"""The decode kernels compile for a TPU v5e at Minitron-8B widths.

Nothing runs: the TPU compiler installed with jax compiles for a chip
that is described, not attached (8 KV heads, 4 query heads per KV
head, head_dim 128, bf16), which catches what interpret mode cannot —
block shapes off the (8, 128) tiling, too much VMEM, 1-D blocks. The
topology is described inside a fixture, never at import, so that only
the test worker that runs this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gqa_decode import gqa_decode
from repro.kernels.paged_decode import paged_gqa_decode

B, H, HKV, HD = 16, 32, 8, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("s_max", [2048, 2000])
def test_gqa_decode_compiles_for_v5e(one_chip, s_max):
    """Contiguous flash decode; 2000 is not a multiple of the 512-token
    block and takes the padded path."""
    args = (_spec(one_chip, (B, H, HD), jnp.bfloat16),
            _spec(one_chip, (B, s_max, HKV, HD), jnp.bfloat16),
            _spec(one_chip, (B, s_max, HKV, HD), jnp.bfloat16),
            _spec(one_chip, (B, s_max), jnp.bool_),
            _spec(one_chip, (B,), jnp.bool_))
    compiled = jax.jit(
        lambda q, k, v, valid, act: gqa_decode(q, k, v, valid, act,
                                               interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("block_s", [16, 128])
def test_paged_gqa_decode_compiles_for_v5e(one_chip, block_s):
    """Paged flash decode over a 32k-token pool, at the engine's
    default 16-token page and at a 128-token page."""
    pages, nb = 32768 // block_s, 2048 // block_s
    args = (_spec(one_chip, (B, H, HD), jnp.bfloat16),
            _spec(one_chip, (pages, block_s, HKV, HD), jnp.bfloat16),
            _spec(one_chip, (pages, block_s, HKV, HD), jnp.bfloat16),
            _spec(one_chip, (B, nb), jnp.int32),
            _spec(one_chip, (B,), jnp.int32),
            _spec(one_chip, (B,), jnp.bool_))
    compiled = jax.jit(
        lambda q, k, v, bt, sl, act: paged_gqa_decode(q, k, v, bt, sl, act,
                                                      interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
