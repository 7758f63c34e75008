"""Exact-parity harness for self-speculative decoding (DESIGN.md
§Speculative decoding).

The engine's contract is that `spec_k > 1` is an invisible
optimization: accepted drafts EQUAL the model's own greedy argmax, so
every output stream must be BITWISE the stream the plain engine
emits — across dense/paged layouts, XLA/Pallas decode backends,
decode_k scan depths, EOS landing inside an accepted window, slot
churn, prefix-cache warm admits, and mesh-sharded engines.

Two model fixtures:

* ``engine_model`` — the reduced llama3 config of
  test_decode_consistency: natural (mostly-rejected) drafting on
  random token streams, the adversarial case for the accept/rewind
  cursor logic.
* ``cyclic_model`` — benchmarks.bench_speculative.agent_loop_model:
  greedy decode walks a fixed token cycle, so prompt-lookup drafts
  are always correct and acceptance is 1.0 BY CONSTRUCTION. This
  makes acceptance-dependent scenarios (EOS inside an accepted
  draft, counter arithmetic, budget clipping at full acceptance)
  deterministic instead of seed-lottery.
"""
import dataclasses

import jax
import numpy as np
import pytest

from conftest import mid_stream_eos, reduced_f32
from repro.models import model as M
from repro.serving.engine import InferenceEngine, ServeRequest
from repro.serving.draft import propose_draft

EOS = 7

multi_device = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs >=4 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8)")


@pytest.fixture(scope="module")
def engine_model():
    cfg = reduced_f32("llama3-70b")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def cyclic_model():
    from benchmarks.bench_speculative import agent_loop_model
    return agent_loop_model()


def _stream(seed=42, n_req=6, max_new=16, l_in_max=40):
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


# ===========================================================================
# bitwise parity: spec_k > 1 == spec_k = 1 == the plain pre-spec engine
# ===========================================================================
@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("decode_k", [1, 4])
@pytest.mark.parametrize("spec_k", [2, 4])
def test_spec_matches_plain(engine_model, paged, decode_k, spec_k):
    """Random streams on the reduced llama: drafts are mostly wrong
    (vocab 1024, little repetition), so this pins the REJECTION path —
    a dead draft must degenerate to plain 1-token decode with the
    rejected tail's KV writes invisible, bitwise."""
    cfg, params = engine_model
    reqs = _stream()
    kw = dict(paged=paged)
    if paged:
        kw["block_size"] = 16
    base, _ = _run_engine(cfg, params, reqs, decode_k=1, spec_k=1, **kw)
    got, eng = _run_engine(cfg, params, reqs, decode_k=decode_k,
                           spec_k=spec_k, **kw)
    assert got == base, \
        f"spec_k={spec_k} decode_k={decode_k} paged={paged} diverged"
    assert eng.spec_stats["verify_windows"] > 0


@pytest.mark.parametrize("paged", [False, True])
def test_spec_pallas_parity(engine_model, paged):
    """The Pallas decode backend routes verify windows through the
    same masked chunk machinery — parity must hold there too."""
    cfg, params = engine_model
    reqs = _stream(seed=3)
    kw = dict(paged=paged)
    if paged:
        kw["block_size"] = 16
    base, _ = _run_engine(cfg, params, reqs, decode_k=1, spec_k=1, **kw)
    got, _ = _run_engine(cfg, params, reqs, decode_k=2, spec_k=4,
                         decode_impl="pallas", **kw)
    assert got == base, f"pallas paged={paged} diverged"


def test_spec_slot_finish_and_readmission(engine_model):
    """More requests than slots with drafting on: slots finishing
    mid-scan (variable advance) must release and re-admit exactly as
    the plain engine does."""
    cfg, params = engine_model
    reqs = _stream(seed=11, n_req=9, max_new=9)
    base, _ = _run_engine(cfg, params, reqs, decode_k=1, spec_k=1)
    got, _ = _run_engine(cfg, params, reqs, decode_k=4, spec_k=4)
    assert got == base
    assert len(got) == len(reqs)


def test_spec_eos_terminates_stream(engine_model):
    """Natural-stream EOS with drafting on: rows stopping at EOS at a
    non-boundary micro-iteration must match the plain engine."""
    cfg, params = engine_model
    reqs = _stream(seed=5, n_req=8, max_new=20)
    free, _ = _run_engine(cfg, params, reqs, eos_id=None, decode_k=1,
                          spec_k=1)
    eos = mid_stream_eos(free.values())
    base, _ = _run_engine(cfg, params, reqs, eos_id=eos, decode_k=1,
                          spec_k=1)
    got, eng = _run_engine(cfg, params, reqs, eos_id=eos, decode_k=4,
                           spec_k=4)
    assert got == base
    assert any(out and out[-1] == eos and len(out) < r["max_new_tokens"]
               for r, out in zip(reqs, base.values())), \
        "stream no longer hits EOS early"
    assert not eng.busy()


def test_spec_prefix_cache_warm_admit(engine_model):
    """A warm (prefix-cached) admission landing while other slots are
    mid-spec-scan must decode the same tokens as a cold plain run."""
    cfg, params = engine_model
    prompt = [int(t) for t in np.random.default_rng(5).integers(1, 900, 37)]
    long_bg = dict(rid=0, tokens=[int(t) for t in
                                  np.random.default_rng(6).integers(1, 900,
                                                                    20)],
                   max_new_tokens=40)
    turn1 = dict(rid=1, tokens=prompt, max_new_tokens=6)
    turn2 = dict(rid=2, tokens=prompt, max_new_tokens=6)

    def run(spec_k, decode_k):
        eng = InferenceEngine(cfg, params, n_max=2, c_max=128, c_chunk=16,
                              eos_id=EOS, paged=True, block_size=16,
                              prefix_cache=True, decode_k=decode_k,
                              spec_k=spec_k)
        eng.submit(ServeRequest(**long_bg))
        eng.submit(ServeRequest(**turn1))
        while 1 not in eng.results:
            eng.step()
        hits_before = eng.prefix_stats["hit_blocks"]
        eng.submit(ServeRequest(**turn2))   # warm admit mid-run
        res = eng.run_to_completion(5000)
        assert eng.prefix_stats["hit_blocks"] > hits_before, \
            "turn 2 did not hit the prefix cache"
        return {rid: r.output_tokens for rid, r in sorted(res.items())}

    assert run(4, 4) == run(1, 1)


# ===========================================================================
# deterministic acceptance scenarios (cyclic model: acceptance == 1.0)
# ===========================================================================
def _cycle_req(cycle, start, max_new, rid=0, l_in=64):
    return dict(rid=rid, tokens=[(start + j) % cycle for j in range(l_in)],
                max_new_tokens=max_new)


def _run_cyclic(cfg, params, reqs, eos_id=None, **kw):
    eng = InferenceEngine(cfg, params, n_max=2, c_max=512, c_chunk=32,
                          eos_id=eos_id, **kw)
    for r in reqs:
        eng.submit(ServeRequest(**r))
    res = eng.run_to_completion(5000)
    return {rid: r.output_tokens for rid, r in sorted(res.items())}, eng


def test_spec_eos_inside_accepted_draft(cyclic_model):
    """EOS emitted as an ACCEPTED DRAFT token (not the bonus): the
    cyclic model emits the cycle deterministically, so placing eos_id
    two tokens past the first decode window's start guarantees the
    proposer drafts it AND the model accepts it mid-window. The device
    must truncate the window's emissions at the EOS and the host must
    finish the slot there — even though later drafts also matched."""
    cfg, params, cycle = cyclic_model
    start = 5
    # prefill emits (start+64) % cycle; eos lands 2 accepted drafts in
    eos = (start + 64 + 2) % cycle
    reqs = [_cycle_req(cycle, start, max_new=32)]
    base, _ = _run_cyclic(cfg, params, reqs, eos_id=eos,
                          decode_k=1, spec_k=1)
    got, eng = _run_cyclic(cfg, params, reqs, eos_id=eos,
                           decode_k=1, spec_k=4)
    assert got == base
    out = got[0]
    assert out[-1] == eos and len(out) == 3 < 32, \
        "scenario drift: EOS no longer lands inside the first window"
    # the EOS really was accepted speculation, not a plain-decode token
    assert eng.spec_stats["accepted_tokens"] >= 1
    assert eng.spec_stats["verify_windows"] >= 1
    # note: eos is also IN the prompt (the prompt covers the whole
    # cycle) — prompt tokens must never terminate a request
    assert eos in reqs[0]["tokens"]


def test_spec_acceptance_counter_arithmetic(cyclic_model):
    """Counter identities on a fully-accepting stream: every proposed
    token is accepted (acceptance == 1.0), kappa == (accepted +
    windows) / windows, and drafted >= proposed >= accepted always."""
    cfg, params, cycle = cyclic_model
    reqs = [_cycle_req(cycle, s, max_new=96, rid=i)
            for i, s in enumerate((0, 17))]
    _, eng = _run_cyclic(cfg, params, reqs, decode_k=4, spec_k=4)
    st = eng.spec_stats
    assert st["accepted_tokens"] <= st["proposed_tokens"] \
        <= st["drafted_tokens"]
    assert st["verify_windows"] > 0
    assert eng.spec_acceptance_rate() == \
        st["accepted_tokens"] / st["proposed_tokens"] == 1.0
    assert eng.spec_kappa() == \
        (st["accepted_tokens"] + st["verify_windows"]) \
        / st["verify_windows"]
    # full windows everywhere except the budget-clipped tail
    assert 3.0 < eng.spec_kappa() <= 4.0
    # a plain engine reports the neutral rates
    _, plain = _run_cyclic(cfg, params, reqs, decode_k=4, spec_k=1)
    assert plain.spec_acceptance_rate() == 0.0
    assert plain.spec_kappa() == 1.0
    assert plain.spec_stats["verify_windows"] == 0


def test_spec_budget_never_exceeded(cyclic_model):
    """Full acceptance would overshoot max_new without the per-window
    budget clip (w <= budget - 1): a 7-token budget under spec_k=8
    chains must emit EXACTLY 7 tokens, matching the plain engine."""
    cfg, params, cycle = cyclic_model
    reqs = [_cycle_req(cycle, 9, max_new=7)]
    base, _ = _run_cyclic(cfg, params, reqs, decode_k=4, spec_k=1)
    got, _ = _run_cyclic(cfg, params, reqs, decode_k=4, spec_k=8)
    assert got == base
    assert len(got[0]) == 7


def test_spec_trace_count_bounded(cyclic_model):
    """The speculative scan keeps the fixed-shape guarantee: ONE
    decode_scan trace (K and W baked in), no plain-decode trace (all
    decode routes through the verify scan), prefill/mixed bounded by
    the bucket count — across admissions, EOS exits and re-admits."""
    cfg, params, cycle = cyclic_model
    reqs = [_cycle_req(cycle, s, max_new=20 + s % 3, rid=i)
            for i, s in enumerate((0, 5, 11, 23))]
    _, eng = _run_cyclic(cfg, params, reqs, eos_id=(11 + 64 + 4) % cycle,
                         decode_k=4, spec_k=4)
    traces = eng.num_compiled_traces()
    assert traces["decode_scan"] <= 1
    assert traces["decode"] == 0
    assert traces["mixed"] <= len(eng.buckets)
    assert traces["prefill"] <= len(eng.buckets)


def test_spec_rejects_windowed_attention(engine_model):
    """Sliding-window ring buffers violate write_chunk_kv's overwrite
    contract (a rejected draft's KV write would alias LIVE history at
    (pos + i) % window), so the engine must refuse the combination at
    construction, not corrupt state at runtime."""
    cfg, params = engine_model
    wcfg = dataclasses.replace(cfg, attention_window=32)
    with pytest.raises(NotImplementedError):
        InferenceEngine(wcfg, params, n_max=2, c_max=128, c_chunk=16,
                        eos_id=EOS, spec_k=4)
    # spec_k == 1 on the same config stays allowed
    InferenceEngine(wcfg, params, n_max=2, c_max=128, c_chunk=16,
                    eos_id=EOS, spec_k=1)


# ===========================================================================
# the draft proposer (deterministic cases; properties in
# test_properties.py)
# ===========================================================================
def test_propose_draft_copies_most_recent_continuation():
    h = [1, 2, 3, 9, 1, 2, 3, 5, 1, 2, 3]
    # trigram [1,2,3] last recurs at index 4..6 -> continuation [5, ...]
    assert propose_draft(h, 4) == [5, 1, 2, 3][:4]
    assert propose_draft(h, 2) == [5, 1]
    # shorter n-grams only used when longer ones miss
    assert propose_draft([7, 7, 1, 2, 3], 2) == []  # suffix [3] unique
    # continuation truncates at end-of-history, never wraps
    assert propose_draft([4, 4, 4], 2) == [4]


def test_propose_draft_degenerate_inputs():
    assert propose_draft([], 4) == []
    assert propose_draft([5], 4) == []
    assert propose_draft([5, 5], 0) == []
    assert propose_draft([5, 5], -1) == []


# ===========================================================================
# mesh-sharded engine + drafting (CI multi-device job: -k sharded)
# ===========================================================================
def _tp_mesh(tp=4):
    from repro.launch.mesh import make_smoke_mesh, make_submeshes
    return make_submeshes(make_smoke_mesh(), tp)[0]


@multi_device
@pytest.mark.parametrize("paged", [False, True])
def test_sharded_spec_token_parity(engine_model, paged):
    """tp=4 mesh engine with drafting on vs the plain 1-device engine:
    the verify windows run under GSPMD sharding and must still emit
    bitwise the sequential stream."""
    cfg, params = engine_model
    reqs = _stream(seed=21, n_req=5, max_new=10)
    kw = dict(paged=paged)
    if paged:
        kw["block_size"] = 16
    base, _ = _run_engine(cfg, params, reqs, decode_k=1, spec_k=1, **kw)
    got, eng = _run_engine(cfg, params, reqs, decode_k=4, spec_k=4,
                           mesh=_tp_mesh(), **kw)
    assert got == base, f"sharded spec paged={paged} diverged"
    assert eng.spec_stats["verify_windows"] > 0


@multi_device
def test_sharded_spec_acceptance(cyclic_model):
    """Full-acceptance chains survive sharding: kappa on the mesh
    engine equals the 1-device kappa on the same cyclic stream."""
    cfg, params, cycle = cyclic_model
    reqs = [_cycle_req(cycle, s, max_new=48, rid=i)
            for i, s in enumerate((3, 31))]
    base, ref = _run_cyclic(cfg, params, reqs, decode_k=4, spec_k=4)
    got, eng = _run_cyclic(cfg, params, reqs, decode_k=4, spec_k=4,
                           mesh=_tp_mesh())
    assert got == base
    assert eng.spec_kappa() == ref.spec_kappa()
    assert eng.spec_acceptance_rate() == 1.0
