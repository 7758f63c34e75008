"""Distributed training launcher.

Runs real pjit-sharded train steps on whatever devices exist (use
XLA_FLAGS=--xla_force_host_platform_device_count=N for a CPU mesh; on
real hardware the same code runs on the production mesh). For CPU
validation use --reduced; the full assigned configs are exercised via
the dry-run.

  PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m repro.launch.train --arch minitron-8b --reduced \
      --steps 10 --mesh 4x2
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.distributed import sharding as SH
from repro.distributed.context import make_context
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.training import checkpoint as CKPT
from repro.training.data import DataConfig, batch_at
from repro.training.optimizer import AdamWConfig, init_adamw
from repro.training.train_step import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minitron-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--mesh", default="",
                    help="DATAxMODEL, e.g. 4x2; default: all devices x 1")
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--zero1", action="store_true",
                    help="shard AdamW m/v over the data axes (ZeRO-1)")
    ap.add_argument("--no-sequence-parallel", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = dataclasses.replace(cfg.reduced(), dtype="float32")
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
    else:
        d, m = jax.device_count(), 1
    mesh = make_mesh((d, m), ("data", "model"))
    ctx = make_context(mesh)
    print(f"mesh {d}x{m} ({jax.device_count()} devices), arch={cfg.name}")

    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    opt = init_adamw(params)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(2, args.steps // 10),
                          total_steps=args.steps)
    step_fn = make_train_step(
        cfg, opt_cfg, parallel=ctx, remat="layer",
        microbatches=args.microbatches,
        sequence_parallel=not args.no_sequence_parallel)

    pspecs = SH.param_specs(jax.eval_shape(lambda: params), ctx)
    ospecs = SH.opt_specs(jax.eval_shape(lambda: opt), pspecs, ctx,
                          zero1=args.zero1)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                    global_batch=args.global_batch)
    b0 = {k: jnp.asarray(v) for k, v in batch_at(dc, 0).items()}
    if cfg.frontend_tokens:
        b0["frontend"] = jnp.ones(
            (args.global_batch, cfg.frontend_tokens, cfg.d_model),
            jnp.dtype(cfg.dtype)) * 0.01
    bspecs = SH.batch_specs(jax.eval_shape(lambda: b0), ctx)
    msh = {k: jax.sharding.PartitionSpec() for k in
           ("ce", "lb_loss", "loss", "grad_norm", "step")}
    jitted = jax.jit(step_fn,
                     in_shardings=SH.to_named((pspecs, ospecs, bspecs), mesh),
                     out_shardings=SH.to_named((pspecs, ospecs, msh), mesh))
    params = jax.device_put(params, SH.to_named(pspecs, mesh))
    opt = jax.device_put(opt, SH.to_named(ospecs, mesh))

    t0 = time.time()
    for i in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in batch_at(dc, i).items()}
        if cfg.frontend_tokens:
            batch["frontend"] = b0["frontend"]
        batch = jax.device_put(batch, SH.to_named(bspecs, mesh))
        params, opt, metrics = jitted(params, opt, batch)
        print(f"step {i:3d} loss={float(metrics['loss']):.4f} "
              f"gnorm={float(metrics['grad_norm']):.2f} "
              f"({(time.time()-t0)/(i+1):.2f}s/step)", flush=True)
    if args.ckpt_dir:
        CKPT.save(args.ckpt_dir, args.steps, jax.device_get(params))
        print(f"saved checkpoint to {args.ckpt_dir}")


if __name__ == "__main__":
    main()
