"""Decode path == teacher-forcing forward (the strongest end-to-end
model correctness check), per family — plus the engine hot-path parity
suite (ISSUE 5): the K-step on-device decode scan and the fused
mixed dispatch must reproduce the K=1 sequential path's output tokens
BITWISE on every configuration."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import mid_stream_eos, reduced_f32
from repro.models import model as M
from repro.serving.engine import InferenceEngine, ServeRequest

FAMS = ["llama3-70b",              # dense GQA
        "qwen1.5-32b",             # MHA + qkv bias
        "deepseek-v2-236b",        # MLA + MoE
        "llama4-scout-17b-a16e",   # MoE top-1 + windowed attention
        "xlstm-350m",              # sLSTM + mLSTM
        "zamba2-1.2b",             # Mamba2 hybrid
        "llama-3.2-vision-11b",    # cross-attn VLM
        "seamless-m4t-large-v2"]   # enc-dec


@pytest.mark.parametrize("name", FAMS)
def test_decode_matches_forward(name, rng_key):
    cfg = reduced_f32(name)
    if cfg.moe is not None:   # disable capacity drops for exactness
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = M.init_params(cfg, rng_key)
    B, S = 2, 16
    toks = jax.random.randint(rng_key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.frontend_tokens:
        batch["frontend"] = jax.random.normal(
            rng_key, (B, cfg.frontend_tokens, cfg.d_model)) * 0.02
    logits_tf, _ = M.forward(params, cfg, batch)

    cache = M.init_cache(cfg, B, 32,
                         frontend_len=cfg.frontend_tokens or None)
    if "xk" in cache:   # cross-attention memories come from prefill
        _, full = M.prefill(params, cfg, batch)
        cache["xk"], cache["xv"] = full["xk"], full["xv"]
    lg = None
    for t in range(S):
        lg, cache = M.decode_step(params, cfg, toks[:, t:t + 1], cache, t)
    ref = np.asarray(logits_tf[:, -1])
    scale = np.max(np.abs(ref)) + 1e-9
    assert np.max(np.abs(np.asarray(lg) - ref)) / scale < 2e-2


@pytest.mark.parametrize("name", ["llama3-70b"])
def test_prefill_matches_forward(name, rng_key):
    cfg = reduced_f32(name)
    params = M.init_params(cfg, rng_key)
    toks = jax.random.randint(rng_key, (2, 16), 0, cfg.vocab_size)
    logits_tf, _ = M.forward(params, cfg, {"tokens": toks})
    last, cache = M.prefill(params, cfg, {"tokens": toks,
                                          "cache_len": 32})
    assert np.allclose(np.asarray(last), np.asarray(logits_tf[:, -1]),
                       atol=1e-4)
    # decode one more token from the prefilled cache vs forward on S+1
    nxt = jnp.zeros((2, 1), jnp.int32)
    lg, _ = M.decode_step(params, cfg, nxt, cache, 16)
    toks2 = jnp.concatenate([toks, nxt], axis=1)
    logits2, _ = M.forward(params, cfg, {"tokens": toks2})
    assert np.allclose(np.asarray(lg), np.asarray(logits2[:, -1]),
                       atol=1e-4)


# ===========================================================================
# engine hot path: K-step decode scan / fused mixed dispatch parity
# ===========================================================================
EOS = 7


@pytest.fixture(scope="module")
def engine_model():
    cfg = reduced_f32("llama3-70b")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _stream(seed=42, n_req=6, max_new=12, l_in_max=40):
    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n_req):
        l_in = int(rng.integers(3, l_in_max))
        reqs.append(dict(rid=rid,
                         tokens=[int(t) for t in rng.integers(1, 900, l_in)],
                         max_new_tokens=int(rng.integers(2, max_new))))
    return reqs


def _run_engine(cfg, params, reqs, eos_id=EOS, **kw):
    eng = InferenceEngine(cfg, params, n_max=3, c_max=128, c_chunk=16,
                          eos_id=eos_id, **kw)
    for r in reqs:
        eng.submit(ServeRequest(**r))
    res = eng.run_to_completion(5000)
    return {rid: r.output_tokens for rid, r in sorted(res.items())}, eng


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("paged", [False, True])
def test_multi_step_scan_matches_sequential(engine_model, impl, paged):
    """K>1 on-device decode scans emit BITWISE the tokens the K=1
    sequential path emits — dense and paged, XLA and Pallas. The
    stream's ragged max_new values make several slots finish mid-scan
    (freeze-on-finish no-op invariant), and the scan path must also
    keep dispatches/token <= 1/K in decode-only steady state."""
    cfg, params = engine_model
    reqs = _stream()
    kw = dict(decode_impl=impl, paged=paged)
    if paged:
        kw["block_size"] = 16
    base, _ = _run_engine(cfg, params, reqs, decode_k=1, **kw)
    for k in (4, 8):
        got, eng = _run_engine(cfg, params, reqs, decode_k=k, **kw)
        assert got == base, f"K={k} diverged from sequential"
        assert eng.dispatches_per_token() <= 1.0 / k, \
            "multi-step scan did not amortize host dispatches"


@pytest.mark.parametrize("family", ["llama4-scout-17b-a16e",   # MoE+window
                                    "llama-3.2-vision-11b"])   # VLM
def test_multi_step_scan_matches_sequential_other_families(family):
    """The engine's other served families route decode through
    decode_step's MoE / windowed / VLM branches and prefill through
    the per-token scan fallback — the K-scan and fused mixed dispatch
    must stay bitwise there too (dense-GQA is covered above)."""
    cfg = reduced_f32(family)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    reqs = _stream(n_req=4, max_new=8)
    base, _ = _run_engine(cfg, params, reqs, decode_k=1)
    got, _ = _run_engine(cfg, params, reqs, decode_k=8)
    assert got == base, f"{family}: K=8 diverged from sequential"


def test_eos_terminates_mid_scan(engine_model):
    """A row emitting EOS at a non-boundary micro-iteration must stop
    exactly there: the emitted tail is discarded, the result matches
    K=1, and the KV slot frees for the next admission."""
    cfg, params = engine_model
    reqs = _stream(seed=5, n_req=8, max_new=20)
    free, _ = _run_engine(cfg, params, reqs, eos_id=None, decode_k=1)
    eos = mid_stream_eos(free.values())
    base, _ = _run_engine(cfg, params, reqs, eos_id=eos, decode_k=1)
    got, eng = _run_engine(cfg, params, reqs, eos_id=eos, decode_k=8)
    assert got == base
    # the stream really exercises EOS mid-stream
    assert any(out and out[-1] == eos and len(out) < r["max_new_tokens"]
               for r, out in zip(reqs, base.values())), \
        "stream no longer hits EOS early"
    assert not eng.busy()


def test_slot_finishing_mid_scan_reuses_slot(engine_model):
    """More requests than slots: slots that finish mid-scan must be
    released and re-admitted (host replay of the device termination),
    with every request's tokens unchanged vs K=1."""
    cfg, params = engine_model
    reqs = _stream(seed=11, n_req=9, max_new=9)
    base, _ = _run_engine(cfg, params, reqs, decode_k=1)
    got, eng = _run_engine(cfg, params, reqs, decode_k=4)
    assert got == base
    assert len(got) == len(reqs)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefix_cache_warm_admit_into_running_scan(engine_model, impl):
    """A warm (prefix-cached) admission landing while other slots are
    mid-decode-scan: the fully/partially cached prompt enters through
    the dirty-tracked device state upload and must decode the same
    tokens as a cold K=1 run."""
    cfg, params = engine_model
    prompt = [int(t) for t in
              np.random.default_rng(5).integers(1, 900, 37)]
    long_bg = dict(rid=0, tokens=[int(t) for t in
                                  np.random.default_rng(6).integers(
                                      1, 900, 20)],
                   max_new_tokens=40)
    turn1 = dict(rid=1, tokens=prompt, max_new_tokens=6)
    turn2 = dict(rid=2, tokens=prompt, max_new_tokens=6)

    def run(decode_k):
        eng = InferenceEngine(cfg, params, n_max=2, c_max=128, c_chunk=16,
                              eos_id=EOS, paged=True, block_size=16,
                              prefix_cache=True, decode_k=decode_k,
                              decode_impl=impl)
        eng.submit(ServeRequest(**long_bg))
        eng.submit(ServeRequest(**turn1))
        # drive until turn1 completes; the background slot keeps the
        # engine in (multi-step) decode
        while 1 not in eng.results:
            eng.step()
        hits_before = eng.prefix_stats["hit_blocks"]
        eng.submit(ServeRequest(**turn2))   # warm admit mid-run
        res = eng.run_to_completion(5000)
        assert eng.prefix_stats["hit_blocks"] > hits_before, \
            "turn 2 did not hit the prefix cache"
        return {rid: r.output_tokens for rid, r in sorted(res.items())}

    assert run(8) == run(1)


def test_scan_trace_count_bounded(engine_model):
    """The new jitted fns keep the fixed-shape guarantee: ONE decode
    scan trace (K baked in), mixed traces bounded by the prefill
    bucket count, across a ragged request mix."""
    cfg, params = engine_model
    reqs = _stream(seed=9, n_req=10, max_new=10, l_in_max=60)
    _, eng = _run_engine(cfg, params, reqs, decode_k=8)
    traces = eng.num_compiled_traces()
    assert traces["decode_scan"] <= 1
    assert traces["mixed"] <= len(eng.buckets)
    assert traces["prefill"] <= len(eng.buckets)
    assert traces["decode"] <= 1


def test_iteration_accounting_multi_step(engine_model):
    """decode_iters stays in ITERATION units (= tokens emitted) at any
    K; the iteration clock advances K per scan dispatch; per-iteration
    utilization is K-invariant (a slot finishing mid-scan stops
    counting at its last decoded iteration, not at the dispatch)."""
    cfg, params = engine_model
    reqs = _stream(seed=21, n_req=3, max_new=16)
    res1, eng1 = _run_engine(cfg, params, reqs, decode_k=1)
    res8, eng8 = _run_engine(cfg, params, reqs, decode_k=8)
    for rid in res1:
        assert len(res8[rid]) == len(res1[rid])
    # queue/decode iters identical per request (iteration clock, not
    # dispatch clock) up to the <K admission-granularity slack
    assert eng8.dispatches < eng1.dispatches
    u1, u8 = eng1.utilization_snapshot(), eng8.utilization_snapshot()
    assert u1 > 0 and u8 > 0
    assert abs(u1 - u8) / u1 < 0.35, (u1, u8)


# ===========================================================================
# mesh-sharded serving parity (ISSUE 6): tp=4 engines must emit BITWISE
# the tokens the 1-device engine emits. Needs >= 4 devices — the CI
# multi-device job fakes 8 via
# XLA_FLAGS=--xla_force_host_platform_device_count=8 (must be set
# before jax imports); on a 1-device host these tests skip and the
# single-device tier is unaffected.
# ===========================================================================
multi_device = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs >= 4 devices (set "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _tp_mesh(tp=4):
    from repro.launch.mesh import make_smoke_mesh, make_submeshes
    return make_submeshes(make_smoke_mesh(), tp)[0]


@multi_device
@pytest.mark.parametrize("decode_k", [1, 4])
@pytest.mark.parametrize("paged", [False, True])
def test_sharded_engine_token_parity(engine_model, paged, decode_k):
    """tp=4 mesh engine vs the plain 1-device engine on the same ragged
    stream: output tokens must match bitwise (GSPMD's row-parallel
    all-reduce perturbs logits in ulps, but greedy argmax tokens are
    pinned — this test is the contract that keeps it that way), for
    dense and paged caches, sequential and K-step scan decode."""
    cfg, params = engine_model
    reqs = _stream()
    kw = dict(paged=paged, decode_k=decode_k)
    if paged:
        kw["block_size"] = 16
    base, _ = _run_engine(cfg, params, reqs, **kw)
    got, eng = _run_engine(cfg, params, reqs, mesh=_tp_mesh(), **kw)
    assert got == base, "tp=4 tokens diverged from 1-device engine"
    assert eng.tp_degree == 4


@multi_device
def test_sharded_cache_is_actually_sharded(engine_model):
    """The KV pool must really split: per-device bytes at tp=4 are 1/4
    of the 1-device engine's cache (kv-head dim sharding, not a
    replicated fallback)."""
    cfg, params = engine_model
    reqs = _stream(n_req=2, max_new=4)
    _, eng1 = _run_engine(cfg, params, reqs, paged=True, block_size=16)
    _, eng4 = _run_engine(cfg, params, reqs, paged=True, block_size=16,
                          mesh=_tp_mesh())
    assert eng4.cache_bytes_per_device() * 4 == eng1.cache_bytes_per_device()
    assert len(eng4.devices()) == 4


@multi_device
def test_sharded_pallas_falls_back_to_xla(engine_model):
    """decode_impl='pallas' on a tp=4 mesh engine raises instead of
    quietly serving through XLA (the kernels' block specs assume an
    unsharded cache); the same engine asked for 'xla' matches the
    1-device Pallas engine's tokens."""
    cfg, params = engine_model
    reqs = _stream(n_req=3, max_new=6)
    with pytest.raises(NotImplementedError, match="decode_impl='xla'"):
        _run_engine(cfg, params, reqs, decode_impl="pallas",
                    mesh=_tp_mesh())
    base, _ = _run_engine(cfg, params, reqs, decode_impl="pallas")
    got, eng = _run_engine(cfg, params, reqs, decode_impl="xla",
                           mesh=_tp_mesh())
    assert eng.decode_impl == "xla"
    assert got == base


@pytest.mark.parametrize("paged", [False, True])
def test_mesh_engine_refuses_pallas(engine_model, paged):
    """Any mesh, even one device, makes the engine refuse 'pallas'."""
    from repro.launch.mesh import make_smoke_mesh, make_submeshes
    cfg, params = engine_model
    mesh = make_submeshes(make_smoke_mesh(), 1)[0]
    with pytest.raises(NotImplementedError, match="decode_impl='xla'"):
        InferenceEngine(cfg, params, n_max=2, c_max=64, c_chunk=16,
                        paged=paged, decode_impl="pallas", mesh=mesh)


@multi_device
def test_sharded_prefix_cache_warm_admit(engine_model):
    """The prefix-cache warm-admit path (dirty-tracked device uploads
    into a running scan) on a tp=4 engine matches the cold 1-device
    run — block tables replicate, shared blocks live in the sharded
    pool."""
    cfg, params = engine_model
    prompt = [int(t) for t in
              np.random.default_rng(5).integers(1, 900, 37)]

    def run(mesh):
        eng = InferenceEngine(cfg, params, n_max=3, c_max=128, c_chunk=16,
                              eos_id=EOS, paged=True, block_size=16,
                              prefix_cache=True, decode_k=4, mesh=mesh)
        eng.submit(ServeRequest(rid=0, tokens=prompt, max_new_tokens=6))
        eng.run_to_completion(5000)          # turn 1 registers its blocks
        eng.submit(ServeRequest(rid=1, tokens=prompt, max_new_tokens=6))
        res = eng.run_to_completion(5000)
        assert eng.prefix_stats["hit_blocks"] > 0, \
            "turn 2 did not hit the prefix cache"
        return {rid: r.output_tokens for rid, r in sorted(res.items())}

    assert run(_tp_mesh()) == run(None)


@multi_device
def test_sharded_fleet_distinct_submeshes(engine_model):
    """FleetRuntime places pool engines on disjoint tp submeshes and
    serves through the gateway unchanged."""
    from repro.serving.pools import FleetRuntime, GatewayRequest
    cfg, params = engine_model
    from repro.launch.mesh import make_smoke_mesh
    rt = FleetRuntime(cfg, params, boundaries=(64,), gammas=(1.5,),
                      n_maxes=(2, 2), c_maxes=(64, 128), c_chunk=16,
                      mesh=make_smoke_mesh(), tp_degree=2)
    place = rt.device_placement()
    ids = [tuple(v) for v in place.values()]
    assert all(len(v) == 2 for v in ids)
    assert len(set(ids)) == len(ids), f"pools share devices: {place}"
    rt.submit(GatewayRequest(0, "short prompt for the short pool", 4))
    out = rt.run(max_iters=2000)
    assert len(out[0].output_tokens) == 4


def test_sliding_window_matches_full_when_window_covers(rng_key):
    cfg = dataclasses.replace(reduced_f32("minitron-8b"),
                              attention_window=64)
    cfg_full = dataclasses.replace(cfg, attention_window=0)
    params = M.init_params(cfg, rng_key)
    toks = jax.random.randint(rng_key, (2, 16), 0, cfg.vocab_size)
    a, _ = M.forward(params, cfg, {"tokens": toks})        # window 64 > 16
    b, _ = M.forward(params, cfg_full, {"tokens": toks})
    assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_sliding_window_restricts_context(rng_key):
    cfg = dataclasses.replace(reduced_f32("minitron-8b"),
                              attention_window=4)
    params = M.init_params(cfg, rng_key)
    t1 = jax.random.randint(rng_key, (1, 16), 0, cfg.vocab_size)
    t2 = t1.at[0, 0].set((t1[0, 0] + 1) % cfg.vocab_size)
    a, _ = M.forward(params, cfg, {"tokens": t1})
    b, _ = M.forward(params, cfg, {"tokens": t2})
    # changing token 0 must NOT affect position 15 (window=4)
    assert np.allclose(np.asarray(a[0, -1]), np.asarray(b[0, -1]), atol=1e-5)
    # ... but must affect position 1
    assert not np.allclose(np.asarray(a[0, 1]), np.asarray(b[0, 1]),
                           atol=1e-5)
