"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret
mode executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

GQA_SHAPES = [  # (b, h, hkv, hd, s, block_s)
    (2, 8, 2, 64, 512, 256),
    (1, 4, 4, 128, 1024, 512),
    (3, 16, 2, 64, 1024, 512),
    (2, 8, 8, 128, 512, 128),
    (1, 2, 1, 64, 256, 256),    # single kv head, single block
    (2, 8, 2, 64, 600, 512),    # context not a block multiple: padded
]


@pytest.mark.parametrize("b,h,hkv,hd,s,blk", GQA_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gqa_decode_allclose(b, h, hkv, hd, s, blk, dtype):
    key = jax.random.PRNGKey(b * 100 + h)
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, h, hd), dtype)
    kc = jax.random.normal(ks[1], (b, s, hkv, hd), dtype)
    vc = jax.random.normal(ks[2], (b, s, hkv, hd), dtype)
    pos = jax.random.randint(ks[3], (b,), 1, s)
    valid = jnp.arange(s)[None, :] <= pos[:, None]
    out = ops.gqa_decode(q, kc, vc, valid, block_s=blk)
    want = ref.gqa_decode_ref(q, kc, vc, valid)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_gqa_decode_ring_validity():
    """Ring-buffer style validity mask (non-prefix) is honored."""
    key = jax.random.PRNGKey(42)
    ks = jax.random.split(key, 4)
    b, h, hkv, hd, s = 2, 4, 2, 64, 512
    q = jax.random.normal(ks[0], (b, h, hd))
    kc = jax.random.normal(ks[1], (b, s, hkv, hd))
    vc = jax.random.normal(ks[2], (b, s, hkv, hd))
    valid = jax.random.bernoulli(ks[3], 0.5, (b, s))
    valid = valid.at[:, 0].set(True)
    out = ops.gqa_decode(q, kc, vc, valid)
    want = ref.gqa_decode_ref(q, kc, vc, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_gqa_decode_matches_model_sdpa():
    """The kernel is a drop-in for layers.decode_attention's XLA path."""
    from conftest import reduced_f32
    from repro.models import model as M
    cfg = reduced_f32("minitron-8b", head_dim=64)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    cache1 = M.init_cache(cfg, 2, 128)
    cache2 = M.init_cache(cfg, 2, 128)
    tok = jnp.zeros((2, 1), jnp.int32)
    for t in range(3):
        a, cache1 = M.decode_step(params, cfg, tok, cache1, t,
                                  decode_impl="xla")
        b, cache2 = M.decode_step(params, cfg, tok, cache2, t,
                                  decode_impl="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("n", [3, 50, 128, 257])
def test_textrank_allclose(n):
    rng = np.random.default_rng(n)
    m = np.abs(rng.normal(size=(n, n))).astype(np.float32)
    m = (m + m.T) / 2
    got = ops.textrank_scores(m)
    want = np.asarray(ref.textrank_ref(jnp.asarray(m)))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_textrank_plugs_into_compressor():
    from repro.core.compression import ExtractiveCompressor, count_tokens
    text = " ".join(f"Sentence number {i} about fleets queues and pools "
                    f"with extra detail {i % 7}." for i in range(30))
    c_np = ExtractiveCompressor()
    c_k = ExtractiveCompressor(textrank_fn=ops.textrank_scores)
    budget = count_tokens(text) // 2
    r1, r2 = c_np.compress(text, budget), c_k.compress(text, budget)
    assert r1.kept_indices == r2.kept_indices   # identical selection
