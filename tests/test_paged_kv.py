"""Paged KV-cache subsystem: kernel vs oracle, paged==dense engine
parity, allocator invariants, paged admission control, capacity
integration (n_max_paged / FleetDES paged), and the cache-donation +
admission-semantics satellites."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from conftest import reduced_f32
from repro.core.profiles import A100_LLAMA70B, TPU_V5E_LLAMA70B
from repro.core.workload import get_workload
from repro.kernels import ops, paged_decode, ref
from repro.models import layers as L
from repro.models import model as M
from repro.serving.engine import InferenceEngine, ServeRequest


@pytest.fixture(scope="module")
def small_model(rng_key=jax.random.PRNGKey(0)):
    cfg = reduced_f32("llama3-70b")
    return cfg, M.init_params(cfg, rng_key)


def _shuffled_tables(rng, b, nb, num_blocks):
    """Non-overlapping, non-contiguous block tables (the layout the
    engine's free list actually produces)."""
    perm = rng.permutation(num_blocks)[: b * nb]
    return jnp.asarray(perm.reshape(b, nb), jnp.int32)


# ------------------------------------------------------------------ kernel
PAGED_SHAPES = [  # (b, h, hkv, hd, block_s, nb, num_blocks)
    (2, 8, 2, 64, 16, 8, 32),
    (1, 4, 4, 128, 32, 4, 8),
    (3, 16, 2, 64, 64, 4, 16),
    (2, 2, 1, 64, 16, 16, 64),   # single kv head, deep table
]
# (shape, seq_lens): each row's entries past its live pages point outside
# the pool, so the kernel passes only if it never reads them. Pages per
# compute block (paged_decode.pages_per_block): 8 heads x 128 at 16-token
# pages put 32 (f32) or 64 (bf16) pages in a block, so the first shape is
# one 40-page block in bf16 and 32 + 8 in f32, and the second is 64 + 8
# in bf16; the third has 128-token pages. The lengths take 1, block_s,
# block_s + 1, a block edge and nb * block_s.
PAGED_EDGE_CASES = [
    ((5, 48, 8, 128, 16, 40, 200), (1, 16, 17, 513, 640)),
    ((4, 16, 8, 128, 16, 72, 288), (1, 17, 1025, 1152)),
    ((4, 8, 2, 64, 128, 4, 16), (1, 128, 129, 512)),
]


def _outside_past_live(bt, seq, bs, num_blocks):
    """Point every block-table entry past a row's live pages outside the
    pool (each row its own out-of-range value)."""
    bt = np.array(bt)
    for i, n in enumerate(seq):
        bt[i, -(-n // bs):] = num_blocks + 1000 * (i + 1)
    return jnp.asarray(bt, jnp.int32)


@pytest.mark.parametrize(
    "b,h,hkv,hd,bs,nb,p,seq",
    [s + (None,) for s in PAGED_SHAPES] + [s + (q,) for s, q in
                                             PAGED_EDGE_CASES])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_gqa_decode_allclose(b, h, hkv, hd, bs, nb, p, seq, dtype):
    key = jax.random.PRNGKey(b * 100 + h)
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, h, hd), dtype)
    kp = jax.random.normal(ks[1], (p, bs, hkv, hd), dtype)
    vp = jax.random.normal(ks[2], (p, bs, hkv, hd), dtype)
    bt = _shuffled_tables(np.random.default_rng(b), b, nb, p)
    if seq is None:
        seq = jax.random.randint(ks[3], (b,), 1, nb * bs + 1)
        bt_ref = bt
    else:
        bt = _outside_past_live(bt, seq, bs, p)
        bt_ref = jnp.clip(bt, 0, p - 1)   # what the oracle gathers is masked
        seq = jnp.asarray(seq, jnp.int32)
    out = ops.paged_gqa_decode(q, kp, vp, bt, seq)
    want = ref.paged_gqa_decode_ref(q, kp, vp, bt_ref, seq)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_paged_kernel_matches_contiguous_kernel():
    """A paged cache whose gathered rows equal a contiguous cache must
    decode to the same outputs as the contiguous gqa_decode kernel."""
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 4)
    b, h, hkv, hd, bs, nb = 3, 8, 2, 64, 32, 8
    s = nb * bs
    q = jax.random.normal(ks[0], (b, h, hd))
    kc = jax.random.normal(ks[1], (b, s, hkv, hd))
    vc = jax.random.normal(ks[2], (b, s, hkv, hd))
    # scatter the contiguous rows into a shuffled block pool
    bt = _shuffled_tables(np.random.default_rng(3), b, nb, b * nb)
    kp = jnp.zeros((b * nb, bs, hkv, hd))
    vp = jnp.zeros((b * nb, bs, hkv, hd))
    for i in range(b):
        for j in range(nb):
            kp = kp.at[bt[i, j]].set(kc[i, j * bs:(j + 1) * bs])
            vp = vp.at[bt[i, j]].set(vc[i, j * bs:(j + 1) * bs])
    pos = jnp.asarray([10, 100, s - 1])
    valid = jnp.arange(s)[None, :] <= pos[:, None]
    want = ops.gqa_decode(q, kc, vc, valid)
    out = ops.paged_gqa_decode(q, kp, vp, bt, pos + 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5)


@pytest.mark.parametrize("active,outside", [
    ((True, False, True), False),
    ((False, False, False), False),
    # inactive rows' tables point wholly outside the pool, active rows'
    # past their live pages: an inactive row copies nothing
    ((True, False, True), True),
    ((False, False, False), True),
])
def test_paged_kernel_inactive_rows_zero(active, outside):
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 3)
    b, h, hkv, hd, bs, nb, p = 3, 8, 2, 64, 16, 4, 16
    q = jax.random.normal(ks[0], (b, h, hd))
    kp = jax.random.normal(ks[1], (p, bs, hkv, hd))
    vp = jax.random.normal(ks[2], (p, bs, hkv, hd))
    bt = _shuffled_tables(np.random.default_rng(1), b, nb, p)
    seq = jnp.asarray([5, 40, 60], jnp.int32)
    active = np.asarray(active)
    bt_kernel = bt
    if outside:
        bt_kernel = _outside_past_live(
            bt, [int(n) if a else 0 for n, a in zip(seq, active)], bs, p)
    out = np.asarray(ops.paged_gqa_decode(q, kp, vp, bt_kernel, seq,
                                          jnp.asarray(active)))
    want = np.asarray(ref.paged_gqa_decode_ref(q, kp, vp, bt, seq))
    np.testing.assert_allclose(out[active], want[active], atol=2e-5)
    assert np.all(out[~active] == 0.0)


def _heads_by_value(block, hkv, tokens):
    """``paged_decode._heads`` on a loaded block: Pallas's TPU interpreter
    reads no reshaped ref, so the copy test picks each head out of the
    block's value instead of by a strided load."""
    x = block[...].astype(jnp.float32).reshape(tokens, hkv, block.shape[-1])
    for g in range(hkv):
        yield x[:, g]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_copies_live_pages_only(monkeypatch, dtype):
    """The kernel's page copies under Pallas's TPU interpreter, where a
    copy lands only when it is waited on, unset VMEM reads NaN and a
    read out of range raises: several blocks a row, a copy running
    ahead into the next live row past inactive and empty ones, and
    block-table entries outside the pool past every live page."""
    monkeypatch.setattr(paged_decode, "_heads", _heads_by_value)
    b, h, hkv, hd, bs, nb, p = 6, 16, 8, 128, 16, 72, 432
    seq = np.asarray([1, 0, 1025, 17, 640, 1152])
    active = np.asarray([True, True, True, False, True, True])
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (b, h, hd), dtype)
    kp = jax.random.normal(ks[1], (p, bs, hkv, hd), dtype)
    vp = jax.random.normal(ks[2], (p, bs, hkv, hd), dtype)
    bt = _shuffled_tables(np.random.default_rng(2), b, nb, p)
    bt = _outside_past_live(bt, np.where(active, seq, 0), bs, p)
    params = pltpu.InterpretParams(dma_execution_mode="on_wait",
                                   uninitialized_memory="nan",
                                   out_of_bounds_reads="raise")
    out = np.asarray(paged_decode.paged_gqa_decode(
        q, kp, vp, bt, jnp.asarray(seq, jnp.int32), jnp.asarray(active),
        interpret=params), np.float32)
    want = np.asarray(ref.paged_gqa_decode_ref(
        q, kp, vp, jnp.clip(bt, 0, p - 1), jnp.asarray(seq, jnp.int32)),
        np.float32)
    live = active & (seq > 0)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out[live], want[live], atol=tol, rtol=tol)
    assert np.all(out[~live] == 0.0)


# ----------------------------------------------------------- paged writes
def test_paged_writes_are_noops_for_inactive_rows():
    """paged_scatter_tokens / write_chunk_kv_paged must leave the block
    pool BIT-IDENTICAL for masked rows and padding (the dense engine's
    no-op invariant, paged edition)."""
    kv = {"k": jax.random.normal(jax.random.PRNGKey(0), (8, 16, 2, 64)),
          "v": jax.random.normal(jax.random.PRNGKey(1), (8, 16, 2, 64))}
    bt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    k_new = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 2, 64))
    v_new = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 2, 64))
    # row 1 has length 0 -> its blocks (2, 3) must be untouched
    out = L.write_chunk_kv_paged(kv, k_new, v_new, bt,
                                 jnp.asarray([3, 0]), jnp.asarray([5, 0]))
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(out[name][2:4]),
                                      np.asarray(kv[name][2:4]))
        # row 0 valid tokens landed at positions 3..7 of its blocks
        got = np.asarray(out[name][jnp.asarray([0, 1])]).reshape(32, 2, 64)
        want = np.asarray(k_new if name == "k" else v_new)[0]
        np.testing.assert_array_equal(got[3:8], want)
    # unallocated pool blocks never move
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(out[name][4:]),
                                      np.asarray(kv[name][4:]))


# ------------------------------------------------------------ engine parity
def _mixed_requests():
    return [dict(rid=0, tokens=[5, 6, 7], max_new_tokens=6),
            dict(rid=1, tokens=list(range(1, 40)), max_new_tokens=5),
            dict(rid=2, tokens=list(range(20, 85)), max_new_tokens=4),
            dict(rid=3, tokens=list(range(9, 18)), max_new_tokens=7)]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_paged_engine_matches_dense_tokens(small_model, impl):
    """Acceptance: on the same request stream, paged mode reproduces
    dense-mode output tokens exactly (both decode impls)."""
    cfg, params = small_model
    outs = {}
    for paged in (False, True):
        eng = InferenceEngine(cfg, params, n_max=3, c_max=128, c_chunk=16,
                              decode_impl=impl, paged=paged)
        for r in _mixed_requests():
            eng.submit(ServeRequest(**r))
        outs[paged] = {k: v.output_tokens
                       for k, v in eng.run_to_completion(1000).items()}
    assert outs[False] == outs[True]


def test_paged_engine_packed_slots_matches_dense(small_model):
    """More slots than a dense layout could hold at the same HBM (the
    paged capacity win) still decodes the same per-request tokens."""
    cfg, params = small_model
    dense = InferenceEngine(cfg, params, n_max=2, c_max=128, c_chunk=16)
    # same HBM: 2 slots * 8 blocks; paged packs 4 slots into it
    paged = InferenceEngine(cfg, params, n_max=4, c_max=128, c_chunk=16,
                            paged=True, block_size=16, num_blocks=16)
    reqs = [dict(rid=i, tokens=list(range(1, 20 + 3 * i)),
                 max_new_tokens=5) for i in range(4)]
    for eng in (dense, paged):
        for r in reqs:
            eng.submit(ServeRequest(**r))
    res_d = {k: v.output_tokens
             for k, v in dense.run_to_completion(1000).items()}
    res_p = {k: v.output_tokens
             for k, v in paged.run_to_completion(1000).items()}
    assert res_d == res_p
    # the packed engine really ran them concurrently (queue_iters == 1
    # is the engine's immediate-admission value: iteration increments
    # before the admit phase)
    assert all(v.queue_iters == 1 for v in paged.results.values())
    assert any(v.queue_iters > 1 for v in dense.results.values())


# ------------------------------------------------------- allocator invariants
def _check_allocator(eng):
    allocated = [b for blocks in eng._slot_blocks for b in blocks]
    assert len(allocated) == len(set(allocated)), "double-allocated block"
    assert not set(allocated) & set(eng._free), "block both free and owned"
    assert len(allocated) + len(eng._free) == eng.num_blocks, "block leak"
    assert 0 <= eng._reserved <= len(eng._free)
    for s, blocks in enumerate(eng._slot_blocks):
        # the block table prefix mirrors the owned-block list
        np.testing.assert_array_equal(eng.block_tables[s, :len(blocks)],
                                      blocks)


def test_allocator_invariants_throughout_run(small_model):
    """Acceptance: no double-allocated block at any iteration, and all
    blocks return to the free list after run_to_completion."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, n_max=3, c_max=64, c_chunk=16,
                          paged=True, block_size=16, num_blocks=9)
    rng = np.random.default_rng(0)
    for rid in range(7):
        eng.submit(ServeRequest(
            rid=rid, tokens=list(rng.integers(1, 900, rng.integers(3, 40))),
            max_new_tokens=int(rng.integers(2, 8))))
    while eng.busy() and eng.iteration < 1000:
        eng.step()
        _check_allocator(eng)
    assert len(eng.results) == 7
    assert sorted(eng._free) == list(range(eng.num_blocks))
    assert eng._reserved == 0
    assert eng.kv_tokens_held() == 0


def test_paged_request_larger_than_pool_is_refused(small_model):
    """A request whose worst case exceeds the WHOLE block pool can
    never be covered — it must be refused (empty result), not deferred
    forever at the FIFO head."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, n_max=2, c_max=128, c_chunk=16,
                          paged=True, block_size=16, num_blocks=2)
    eng.submit(ServeRequest(rid=0, tokens=list(range(1, 60)),
                            max_new_tokens=10))   # needs 5 blocks > 2
    eng.submit(ServeRequest(rid=1, tokens=[1, 2, 3], max_new_tokens=2))
    res = eng.run_to_completion(200)
    assert res[0].output_tokens == []
    assert len(res[1].output_tokens) == 2
    assert not eng._enqueued_at and eng._reserved == 0


def test_paged_admission_control_defers_not_preempts(small_model):
    """A request whose worst-case blocks the free list cannot cover
    stays QUEUED (FIFO) until completions return blocks — it is never
    refused and nothing in flight is preempted."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, n_max=3, c_max=128, c_chunk=16,
                          paged=True, block_size=16, num_blocks=4)
    for rid in range(3):
        eng.submit(ServeRequest(rid=rid, tokens=list(range(1, 40)),
                                max_new_tokens=5))
    res = eng.run_to_completion(2000)
    assert sorted(res) == [0, 1, 2]
    assert all(len(res[r].output_tokens) == 5 for r in res)
    assert res[1].queue_iters > 0 and res[2].queue_iters > res[1].queue_iters
    assert sorted(eng._free) == list(range(4))


# ------------------------------------- admission semantics (satellite fix)
def test_refused_request_does_not_stall_next(small_model):
    """An oversized direct-submitted request must not consume the
    slot's admit chance: the next waiting request takes the slot in the
    SAME iteration (the seed engine left it idle one extra step)."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, n_max=1, c_max=32, c_chunk=16)
    eng.submit(ServeRequest(rid=0, tokens=list(range(1, 40)),
                            max_new_tokens=10))        # oversized
    eng.submit(ServeRequest(rid=1, tokens=[1, 2, 3], max_new_tokens=2))
    eng.step()
    assert eng.results[0].output_tokens == []          # refused
    assert eng.slot_req[0] is not None and eng.slot_req[0].rid == 1
    res = eng.run_to_completion(100)
    assert res[1].queue_iters == 1     # immediate admission, no stall


def test_refused_request_leaks_no_host_state(small_model):
    """Refusal must delete the rid's _enqueued_at/_queue_iters entries
    (long-lived engines served years of traffic would otherwise grow
    host dicts without bound); completions clean up too."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, n_max=1, c_max=32, c_chunk=16)
    eng.submit(ServeRequest(rid=7, tokens=list(range(1, 40)),
                            max_new_tokens=10))        # oversized
    eng.submit(ServeRequest(rid=8, tokens=[1, 2, 3], max_new_tokens=2))
    eng.run_to_completion(100)
    assert len(eng.results) == 2
    assert not eng._enqueued_at and not eng._queue_iters
    assert not eng._prefill_iters


# --------------------------------------------------- cache donation satellite
def test_step_fns_donate_cache_buffer(small_model):
    """Both jitted step functions must mark the cache pytree as donated
    (input-output aliased) so XLA reuses its HBM instead of holding two
    full copies across every step. CPU ignores donation at runtime, so
    the check is on the lowered HLO."""
    cfg, params = small_model
    for paged in (False, True):
        eng = InferenceEngine(cfg, params, n_max=2, c_max=64, c_chunk=16,
                              paged=paged)
        toks = jnp.zeros((2, 1), jnp.int32)
        pos = jnp.zeros((2,), jnp.int32)
        act = jnp.ones((2,), bool)
        if paged:
            args = (eng.params, eng.cache, toks,
                    jnp.asarray(eng.block_tables), pos, act)
        else:
            args = (eng.params, eng.cache, toks, pos, act)
        txt = eng._decode.lower(*args).as_text()
        assert "tf.aliasing_output" in txt, \
            f"decode cache not donated (paged={paged})"
        tokens = jnp.zeros((eng.n_max, 16), jnp.int32)
        lens = jnp.zeros((eng.n_max,), jnp.int32)
        if paged:
            pargs = (eng.params, eng.cache, tokens,
                     jnp.asarray(eng.block_tables), pos, lens)
        else:
            pargs = (eng.params, eng.cache, tokens, pos, lens)
        txt = eng._prefill_step.lower(*pargs).as_text()
        assert "tf.aliasing_output" in txt, \
            f"prefill cache not donated (paged={paged})"


def test_no_cache_buffer_accumulation_across_steps(small_model):
    """Steady-state stepping must not accumulate live cache-sized
    device buffers (donation + reassignment: at most the current cache
    plus one in-flight copy exist)."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, n_max=2, c_max=64, c_chunk=16)
    eng.submit(ServeRequest(rid=0, tokens=[1, 2, 3], max_new_tokens=40))
    for _ in range(5):
        eng.step()
    leaf_nbytes = eng.cache["kv"]["k"].nbytes

    def live_kv_leaves():
        return sum(1 for a in jax.live_arrays()
                   if a.nbytes == leaf_nbytes)
    before = live_kv_leaves()
    for _ in range(10):
        eng.step()
    assert live_kv_leaves() <= before + 2    # current k/v at most once more


# --------------------------------------------------- capacity integration
def test_n_max_paged_beats_dense_on_paper_mixes():
    """Acceptance: >= 1.5x effective slots per GPU at equal HBM on the
    lmsys and azure length mixes, both pools."""
    for wname in ("lmsys", "azure"):
        w = get_workload(wname)
        l_total, _, _ = w.sample_arrays(50_000, seed=0)
        for pool, c_max in (("short", w.b_short), ("long", 65536)):
            sel = l_total <= w.b_short if pool == "short" \
                else l_total > w.b_short
            mean_tok = float(l_total[sel].mean())
            ratio = A100_LLAMA70B.n_max_paged(mean_tok) \
                / A100_LLAMA70B.n_max(c_max)
            assert ratio >= 1.5, (wname, pool, ratio)


def test_n_max_paged_properties():
    p = A100_LLAMA70B
    # monotone: longer mixes -> fewer slots; never below 1
    assert p.n_max_paged(500) > p.n_max_paged(5000) > p.n_max_paged(60000)
    assert p.n_max_paged(1e9) == 1
    # a mix at the worst case erases the advantage (same budget)
    assert p.n_max_paged(p.c_ref, tail_margin_blocks=0) == p.n_ref
    # bytes accounting matches the token accounting
    assert p.kv_bytes_per_slot_paged(4096) \
        == p._paged_slot_tokens(4096) * p.kv_bytes_per_token
    # context-scaled H: paged iteration reads ~mean tokens per slot
    assert TPU_V5E_LLAMA70B.t_iter_paged(2048) > 0


def test_fleet_des_paged_runs_and_packs_more_slots():
    from repro.core.planner import fleetopt_plan
    from repro.sim.des import FleetDES
    w = get_workload("lmsys")
    plan, _ = fleetopt_plan(w, lam=200.0, fixed_b=w.b_short)
    dense = FleetDES(plan, workload=w, gamma=1.0, max_sim_slots=512)
    paged = FleetDES(plan, workload=w, gamma=1.0, max_sim_slots=512,
                     paged=True)
    sd = dense.run(n_requests=4000, lam=200.0, seed=1)
    sp = paged.run(n_requests=4000, lam=200.0, seed=1)
    assert set(sd) == set(sp)
    for name in sd:
        # paged pools time-share the same arrivals over MORE slots ->
        # utilization strictly drops (same traffic, bigger fleet)
        assert 0.0 <= sp[name].utilization <= sd[name].utilization + 1e-9


# --------------------------------------- prefill bucket edges (satellite)
def test_prefill_buckets_edge_cases():
    from repro.serving.engine import prefill_buckets
    # c_chunk below min_bucket: the single bucket IS c_chunk
    assert prefill_buckets(3) == (3,)
    assert prefill_buckets(8) == (8,)
    # non-power-of-two c_chunk: pow2 ladder, then c_chunk itself
    assert prefill_buckets(24) == (8, 16, 24)
    assert prefill_buckets(100) == (8, 16, 32, 64, 100)
    for c in (3, 7, 12, 24, 100, 512):
        bs = prefill_buckets(c)
        assert bs[-1] == c and all(b <= c for b in bs)
        assert list(bs) == sorted(set(bs)), bs     # strictly increasing


@pytest.mark.parametrize("c_chunk", [6, 24])
def test_engine_with_odd_c_chunk(small_model, c_chunk):
    """Engine runs (and bounds its traces) with c_chunk below
    min_bucket and non-power-of-two — every chunk still pads to a
    bucket that fits."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, n_max=2, c_max=128,
                          c_chunk=c_chunk)
    for rid, n_tok in enumerate([3, 11, 29]):
        eng.submit(ServeRequest(rid=rid, tokens=list(range(1, n_tok + 1)),
                                max_new_tokens=2))
    res = eng.run_to_completion(500)
    assert len(res) == 3
    assert all(len(r.output_tokens) == 2 for r in res.values())
    assert res[2].prefill_iters == -(-29 // c_chunk)
    assert eng.prefill_buckets_used <= set(eng.buckets)


def test_two_pool_runtime_paged_matches_dense(small_model):
    """End-to-end: the gateway + engines stack produces identical
    outputs with paged engines underneath."""
    from repro.serving.pools import GatewayRequest, TwoPoolRuntime
    cfg, params = small_model

    def make_rt(paged):
        return TwoPoolRuntime(cfg, params, b_short=256, gamma=1.5,
                              n_max_short=4, n_max_long=2,
                              c_max_long=2048, c_chunk=64, paged=paged)

    border = " ".join(
        f"Background sentence {i} with detail about topic {i % 5} and some "
        f"padding words for length." for i in range(13))
    reqs = [GatewayRequest(rid=0, text="short question",
                           max_output_tokens=4),
            GatewayRequest(rid=1, text=border, max_output_tokens=8),
            GatewayRequest(rid=2, text=border * 4, max_output_tokens=8)]
    outs = {}
    for paged in (False, True):
        rt = make_rt(paged)
        for r in reqs:
            rt.submit(r)
        res = rt.run(max_iters=3000)
        outs[paged] = {k: (v.pool, v.output_tokens) for k, v in res.items()}
    assert outs[False] == outs[True]


# ----------------------------------------------------- paged cache gating
def test_init_paged_cache_gates_unsupported_families():
    cfg = reduced_f32("qwen1.5-32b", attention_window=64)
    with pytest.raises(NotImplementedError):
        M.init_paged_cache(cfg, 8, 16)
