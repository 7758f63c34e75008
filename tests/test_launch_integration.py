"""Launch-stack integration: the dry-run machinery end-to-end on a
small fake-device mesh (subprocess: jax pins device count at init)."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax
from repro.configs.base import get_config, INPUT_SHAPES
from repro.distributed import sharding as SH
from repro.distributed.context import make_context
from repro.launch import dryrun as DR
from repro.launch import input_specs as IS
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
ctx = make_context(mesh)
shape = dataclasses.replace(INPUT_SHAPES["decode_32k"], seq_len=256,
                            global_batch=8)
for arch in ("minitron-8b", "deepseek-v2-236b", "zamba2-1.2b"):
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    step, args, in_sh, out_sh = DR.build_step(cfg, shape, ctx)
    c = jax.jit(step, in_shardings=SH.to_named(in_sh, mesh),
                out_shardings=SH.to_named(out_sh, mesh)).lower(*args).compile()
    assert c.cost_analysis()["flops"] > 0
    coll = DR.collective_bytes(c.as_text())
    assert isinstance(coll, dict)
# train kind too (exercises remat+seq-par+opt specs)
tshape = dataclasses.replace(INPUT_SHAPES["train_4k"], seq_len=64,
                             global_batch=8)
cfg = dataclasses.replace(get_config("minitron-8b").reduced(),
                          dtype="float32")
step, args, in_sh, out_sh = DR.build_step(cfg, tshape, ctx)
c = jax.jit(step, in_shardings=SH.to_named(in_sh, mesh),
            out_shardings=SH.to_named(out_sh, mesh)).lower(*args).compile()
print("LAUNCH_INTEGRATION_OK")
"""


@pytest.mark.slow
def test_dryrun_stack_small_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    # generous timeout: compile-bound subprocess on a cpu-share
    # throttled box (see test_moe_sharded.py)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1800)
    assert "LAUNCH_INTEGRATION_OK" in out.stdout, out.stdout + out.stderr
