"""Launcher plumbing that decides where work runs: the kernels' interpret
switch, the compile-cache location, the launcher's chip-size flags, and
the sharded benchmark's refusal to re-exec onto the CPU from a chip."""
import types

import jax
import pytest

from repro.kernels import ops
from repro.launch import compile_cache, serve


def _fake_jax(platform, platforms):
    return types.SimpleNamespace(
        default_backend=lambda: platform,
        config=types.SimpleNamespace(jax_platforms=platforms))


@pytest.mark.parametrize("platform,platforms,interpret", [
    ("cpu", "cpu", True),       # tests: the CPU asked for explicitly
    ("tpu", None, False),       # the chip: compiled kernels
    ("tpu", "tpu", False),
])
def test_interpret_mode(monkeypatch, platform, platforms, interpret):
    monkeypatch.setattr(ops, "jax", _fake_jax(platform, platforms))
    assert ops.interpret_mode() is interpret


@pytest.mark.parametrize("platform,platforms", [
    ("cpu", None),              # no accelerator found: JAX fell back
    ("cpu", ""),
    ("gpu", None),              # another accelerator
])
def test_interpret_mode_refuses(monkeypatch, platform, platforms):
    monkeypatch.setattr(ops, "jax", _fake_jax(platform, platforms))
    with pytest.raises(RuntimeError, match="need a TPU"):
        ops.interpret_mode()


@pytest.fixture
def cache_dir_restored():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_wins(monkeypatch, tmp_path, cache_dir_restored):
    """With JAX_COMPILATION_CACHE_DIR set, JAX owns the location and
    the helper sets nothing."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_fixed_path(monkeypatch, cache_dir_restored):
    """Unset, the cache lands at one fixed, gitignored path inside the
    checkout."""
    import os
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split(), "cache not gitignored"


def test_chip_size_flags_reach_the_engines():
    """--layers/--slots/--top-context/--c-chunk/--decode-impl size the
    fleet; the defaults are the CPU demo's."""
    args = serve.parse_args([
        "--reduced", "--layers", "1", "--fleet", "2", "--paged",
        "--decode-impl", "pallas", "--slots", "3,1",
        "--top-context", "1024", "--c-chunk", "32"])
    rt, plan = serve.build_fleet_runtime(args)
    engines = list(rt.engines.values())
    assert rt.cfg.num_layers == 1
    assert [e.n_max for e in engines] == [3, 1]
    assert engines[-1].c_max == 1024
    assert engines[0].c_max == plan.boundaries[0] * 1024 \
        // plan.pools[-1].c_max
    assert all(e.decode_impl == "pallas" and e.c_chunk == 32
               for e in engines)
    demo = serve.parse_args(["--fleet", "2"])
    assert (demo.slots, demo.top_context, demo.c_chunk, demo.layers,
            demo.decode_impl) == ("2", 512, 16, 0, "xla")


def test_slot_caps_must_match_pools():
    from repro.core.planner import plan_k_pool
    from repro.core.workload import get_workload
    from repro.serving.pools import FleetRuntime
    plan = plan_k_pool(get_workload("azure"), lam=1000.0, t_slo=0.5, k=2)
    with pytest.raises(ValueError, match="slot caps"):
        FleetRuntime.from_plan(None, None, plan, slots_per_pool=[1, 2, 3])


def test_sharded_bench_refuses_chip_parent(monkeypatch):
    """A parent on an accelerator with < 4 devices must not start a
    CPU child (it would benchmark the host, not the chip)."""
    from benchmarks import bench_sharded_serving
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(bench_sharded_serving.subprocess, "run",
                        lambda *a, **k: pytest.fail("started a child"))
    with pytest.raises(RuntimeError, match=">= 4 tpu devices"):
        bench_sharded_serving.run(quick=True)
