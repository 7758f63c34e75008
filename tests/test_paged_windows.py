"""Sliding windows per layer in the paged path, and the dropless
held-expert layer's grouped matmul.

* ``paged_gqa_decode(window=w)`` against its oracle for windows below,
  at and across a page edge, and under Pallas's TPU interpreter with
  every page behind the window outside the pool and every key behind it
  NaN: it reads neither, and copies at most ceil((w + 15) / 16) pages.
* The XLA paged decode and prefill paths mask the same band.
* ``expert_gmm`` against its oracle.
* Dense configurations run the same step programs as before windows and
  experts came into the paged path: their lowered text is pinned.
"""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from conftest import reduced_f32
from repro.kernels import ops, paged_decode, ref
from repro.models import layers as L
from repro.models import model as M
from test_paged_kv import _heads_by_value

BS = 16


def _pool(seed, b, h, hkv, hd, p, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, h, hd), dtype),
            jax.random.normal(ks[1], (p, BS, hkv, hd), dtype),
            jax.random.normal(ks[2], (p, BS, hkv, hd), dtype))


def _tables(b, nb, p, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.stack([rng.permutation(p)[:nb]
                                 for _ in range(b)]), jnp.int32)


SEQS = [1, 7, 8, 9, 15, 16, 17, 23, 24, 25, 33, 40, 100, 128]


@pytest.mark.parametrize("window", [8, 16, 24, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_windowed_paged_decode_matches_oracle(window, dtype):
    """Windows below a page (8), one page (16) and across a page edge
    (24); seq lens on both sides of every edge."""
    b, h, hkv, hd, nb, p = len(SEQS), 8, 2, 64, 8, 160
    q, kp, vp = _pool(window, b, h, hkv, hd, p, dtype)
    bt = _tables(b, nb, p)
    seq = jnp.asarray(SEQS, jnp.int32)
    out = ops.paged_gqa_decode(q, kp, vp, bt, seq, window=window)
    want = ref.paged_gqa_decode_ref(q, kp, vp, bt, seq, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [8, 24])
def test_windowed_sweep_reads_nothing_behind_the_window(monkeypatch,
                                                        window):
    """Under the TPU interpreter (a copy lands when waited on, unset
    VMEM reads NaN, a read out of range raises): every block-table entry
    for a page wholly behind a row's window points outside the pool,
    and every key and value behind the window is NaN. The output is
    finite and equals the oracle's on the clean pool."""
    monkeypatch.setattr(paged_decode, "_heads", _heads_by_value)
    b, h, hkv, hd, nb, p = 5, 16, 8, 128, 8, 64
    seq = np.asarray([3, 17, 40, 100, 128])
    q, kp, vp = _pool(3, b, h, hkv, hd, p)
    # rows own disjoint pages, so a NaN behind one row's window lies
    # in no other row's
    bt = np.random.default_rng(4).permutation(p)[:b * nb].reshape(b, nb)
    dirty_k, dirty_v, bt_k = np.array(kp), np.array(vp), bt.copy()
    for r, n in enumerate(seq):
        first = max(n - window, 0)
        bt_k[r, :first // BS] = p + 3 + r            # outside the pool
        assert (n + BS - 1) // BS - first // BS <= -(-(window + BS - 1)
                                                      // BS)
        for t in range(first):                     # keys behind: NaN
            dirty_k[bt[r, t // BS], t % BS] = np.nan
            dirty_v[bt[r, t // BS], t % BS] = np.nan
    params = pltpu.InterpretParams(dma_execution_mode="on_wait",
                                   uninitialized_memory="nan",
                                   out_of_bounds_reads="raise")
    out = np.asarray(paged_decode.paged_gqa_decode(
        q, jnp.asarray(dirty_k), jnp.asarray(dirty_v), jnp.asarray(bt_k),
        jnp.asarray(seq, jnp.int32), interpret=params, window=window))
    want = np.asarray(ref.paged_gqa_decode_ref(
        q, kp, vp, jnp.asarray(bt), jnp.asarray(seq, jnp.int32),
        window=window))
    assert np.all(np.isfinite(out))
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_the_cells_window_copies_at_most_nine_pages():
    """128 keys over 16-token pages: a row's window spans at most 9
    pages, and its compute block holds them (16 pages, whole 128-token
    lane tiles), far less than a full layer's."""
    assert max(-(-n // BS) - max(n - 128, 0) // BS
               for n in range(1, 5000)) == 9
    assert paged_decode.pages_per_block(256, BS, 8, 128, 2, 128) == 16
    assert paged_decode.pages_per_block(256, BS, 8, 128, 2) == 64


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_paged_decode_attention_masks_the_band(impl):
    """One layer's decode against the paged pool equals attention over
    the gathered row with the band mask, both paths."""
    cfg = reduced_f32("minitron-8b", head_dim=64)
    attn = L.init_attention(jax.random.PRNGKey(1), cfg)
    b, nb, p, window = 3, 8, 32, 24
    kv = {"k": jax.random.normal(jax.random.PRNGKey(2),
                                 (p, BS, cfg.num_kv_heads, 64)),
          "v": jax.random.normal(jax.random.PRNGKey(3),
                                 (p, BS, cfg.num_kv_heads, 64))}
    bt = _tables(b, nb, p)
    pos = jnp.asarray([5, 40, 100], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(4), (b, 1, cfg.d_model))
    out, new = L.paged_decode_attention(attn, cfg, x, kv, bt, pos,
                                        decode_impl=impl, window=window)
    full, _ = L.paged_decode_attention(attn, cfg, x, kv, bt, pos,
                                       decode_impl=impl)
    q, _, _ = L._qkv(attn, cfg, x, x)
    q = L.apply_rope(q, pos[:, None], cfg.rope_theta)
    k_all, v_all = L.gather_pages(new["k"], bt), L.gather_pages(new["v"], bt)
    j = jnp.arange(nb * BS)[None, :]
    band = (j <= pos[:, None]) & (j > pos[:, None] - window)
    want = L._sdpa(q, k_all, v_all, band[:, None, :], cfg.q_per_kv) \
        @ attn["wo"]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert not np.allclose(np.asarray(out), np.asarray(full), atol=1e-3)


# ---------------------------------------------------------- expert_gmm
@pytest.mark.parametrize("m,kd,n,e,tm", [(64, 64, 96, 4, 16),
                                         (256, 256, 384, 8, 32),
                                         (128, 1024, 128, 3, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_expert_gmm_matches_oracle(m, kd, n, e, tm, dtype):
    ks = jax.random.split(jax.random.PRNGKey(m + e), 3)
    x = jax.random.normal(ks[0], (m, kd), dtype)
    w = jax.random.normal(ks[1], (e, kd, n), dtype)
    te = jax.random.randint(ks[2], (m // tm,), 0, e)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    for live in sorted({0, 1, m // tm // 2, m // tm}):
        out = np.asarray(ops.expert_gmm(x, w, te, jnp.int32(live), tm,
                                        jnp.float32))
        want = np.asarray(ref.expert_gmm_ref(x, w, te, live, tm))
        r = live * tm
        scale = np.abs(want[:r]).max() if r else 1.0
        np.testing.assert_allclose(out[:r] / scale, want[:r] / scale,
                                   atol=tol, rtol=tol)


# ------------------------------------------- dense programs unchanged
# sha256 of the lowered text of the paged step programs of a reduced
# dense model, as they were before per-layer windows, leading dense
# layers and the held-expert layer came into the paged path
DENSE_PROGRAMS = {
    "decode_pallas":
        "2dc41c729f183335cd61b626dfecb63ed8be8032168c6267fcb997694f79835f",
    "decode_xla":
        "62de5ba01599b12da554fb3d515d78c17aab637e882840d3276f9d6661a1ead1",
    "mixed_pallas":
        "1cf617987bcf32bb7b334b999fa4ef03d53aec65191c6da3630dec78cb1728d6",
    "mixed_xla":
        "614ef1500881c4169926971d1b674a555fbf144e7ef8c999ca14bc360f0fc50f",
    "prefill":
        "e620458e587b34765e26b5df47755f3152a9ae4e1eb51caa7ad7dacd382a6562",
}


def _dense_programs():
    from repro.configs.base import get_config
    cfg = dataclasses.replace(get_config("minitron-8b").reduced(),
                              dtype="float32")
    params = jax.eval_shape(lambda: M.init_params(cfg,
                                                  jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: M.init_paged_cache(cfg, 32, 16))
    b, nb, ln = 4, 8, 16

    def i32(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32)
    act = jax.ShapeDtypeStruct((b,), jnp.bool_)
    out = {}
    for impl in ("xla", "pallas"):
        def dec(p, c, t, bt, pos, a):
            return M.paged_decode_step(p, cfg, t, c, bt, pos,
                                       decode_impl=impl, active=a)

        def mix(p, c, t, bt, pos, n, dt, a):
            return M.paged_mixed_step(p, cfg, t, c, bt, pos, n, dt, a,
                                      decode_impl=impl)
        out["decode_" + impl] = jax.jit(dec).lower(
            params, cache, i32(b, 1), i32(b, nb), i32(b), act).as_text()
        out["mixed_" + impl] = jax.jit(mix).lower(
            params, cache, i32(b, ln), i32(b, nb), i32(b), i32(b),
            i32(b, 1), act).as_text()

    def pre(p, c, t, bt, st, n):
        return M.paged_prefill_chunk(p, cfg, t, c, bt, st, n)
    out["prefill"] = jax.jit(pre).lower(params, cache, i32(b, ln),
                                        i32(b, nb), i32(b),
                                        i32(b)).as_text()
    return out


def test_dense_step_programs_are_unchanged():
    got = {k: hashlib.sha256(v.encode()).hexdigest()
           for k, v in _dense_programs().items()}
    assert got == DENSE_PROGRAMS


def test_dense_layout_refuses_per_layer_windows():
    cfg = reduced_f32("minitron-8b", layer_windows=(8, 0))
    with pytest.raises(NotImplementedError):
        M.init_cache(cfg, 2, 64)
    cfg = reduced_f32("minitron-8b", dense_layers=1)
    with pytest.raises(NotImplementedError):
        M.init_cache(cfg, 2, 64)
