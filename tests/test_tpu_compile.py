"""The decode kernels compile for a TPU v5e at Minitron-8B widths, and
the windowed decode and the experts' grouped matmul at K-EXAONE's.

Nothing runs: the TPU compiler installed with jax compiles for a chip
that is described, not attached (8 KV heads, 4 or 6 query heads per KV
head, head_dim 128, bf16), which catches what interpret mode cannot —
block shapes off the (8, 128) tiling, too much VMEM, 1-D blocks. The
topology is described inside a fixture, never at import, so that only
the test worker that runs this file loads the TPU library.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.expert_gmm import expert_gmm
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


def _pool_copies(hlo: str, pool_shape) -> list:
    """Instructions other than the pool's parameters and bitcasts whose
    result holds as many elements as the page pool: a transpose or copy
    of the pool in another layout."""
    size = math.prod(pool_shape)
    found = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* (\S+?)\(", line)
        if m and m.group(2) not in ("parameter", "bitcast") and math.prod(
                int(d) for d in m.group(1).split(",") if d) == size:
            found.append(line.strip()[:160])
    return found


def _compile_paged(sharding, b, h, pages, block_s, nb, window=None):
    args = (_spec(sharding, (b, h, HD), jnp.bfloat16),
            _spec(sharding, (pages, block_s, HKV, HD), jnp.bfloat16),
            _spec(sharding, (pages, block_s, HKV, HD), jnp.bfloat16),
            _spec(sharding, (b, nb), jnp.int32),
            _spec(sharding, (b,), jnp.int32),
            _spec(sharding, (b,), jnp.bool_))
    hlo = jax.jit(
        lambda q, k, v, bt, sl, act: paged_gqa_decode(
            q, k, v, bt, sl, act, interpret=False, window=window)
    ).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert not _pool_copies(hlo, (pages, block_s, HKV, HD))


@pytest.mark.parametrize("block_s", [16, 128])
def test_paged_gqa_decode_compiles_for_v5e(one_chip, block_s):
    """Paged flash decode over a 32k-token pool, at the engine's
    default 16-token page and at a 128-token page."""
    _compile_paged(one_chip, B, H, 32768 // block_s, block_s, 2048 // block_s)


@pytest.mark.parametrize("b,nb", [(16, 256), (2, 1024)])
def test_paged_gqa_decode_compiles_at_benchmark_shapes(one_chip, b, nb):
    """The benchmark's pools: 16 short slots of 256 pages and 2 long
    slots of 1,024, 16-token pages in a 2,048-page pool, 48/8 heads x
    128. The kernel reads the token-major pool where it lies: no copy of
    it in another layout is left in the module."""
    _compile_paged(one_chip, b, 48, 2048, 16, nb)


@pytest.mark.parametrize("b,nb", [(16, 256), (2, 1024)])
def test_windowed_paged_decode_compiles_at_kexaone_shapes(one_chip, b, nb):
    """K-EXAONE's windowed layers: 64/8 heads x 128, a 128-key window,
    the benchmark's pools."""
    _compile_paged(one_chip, b, 64, 2048, 16, nb, window=128)


@pytest.mark.parametrize("rows,tm", [(128, 16), (8192, 128)])
@pytest.mark.parametrize("kd,n", [(6144, 2048), (2048, 6144)])
def test_expert_gmm_compiles_at_kexaone_shapes(one_chip, rows, tm, kd, n):
    """The held experts' grouped matmul: a decode pass (16 rows, tiles
    of 16) and a 1,024-token prefill chunk (tiles of 128), gate/up
    (6,144 -> 2,048) and down (2,048 -> 6,144), 4 layers x 8 experts."""
    args = (_spec(one_chip, (rows, kd), jnp.bfloat16),
            _spec(one_chip, (32, kd, n), jnp.bfloat16),
            _spec(one_chip, (rows // tm,), jnp.int32),
            _spec(one_chip, (), jnp.int32))
    hlo = jax.jit(lambda x, w, te, live: expert_gmm(
        x, w, te, live, tm=tm, out_dtype=jnp.float32, interpret=False)
    ).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
