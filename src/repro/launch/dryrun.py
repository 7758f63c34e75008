import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: prove every (architecture x input shape x mesh)
combination lowers and compiles, and extract the roofline terms.

MUST be run as its own process (the XLA_FLAGS line above executes
before any jax import — jax locks the device count on first init):

  PYTHONPATH=src python -m repro.launch.dryrun --arch minitron-8b \
      --shape train_4k [--multi-pod] [--out benchmarks/results/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all

Outputs one JSON per combination with:
  memory_analysis  (bytes per device: args/outputs/temps/code)
  cost_analysis    (HLO FLOPs + bytes accessed, per-device program)
  collectives      (per-op-type operand bytes parsed from the
                    post-SPMD optimized HLO — per device, per step)
"""
import argparse     # noqa: E402
import dataclasses  # noqa: E402
import json         # noqa: E402
import re           # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402

import jax          # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import INPUT_SHAPES, get_config, list_configs  # noqa: E402
from repro.distributed import sharding as SH                           # noqa: E402
from repro.distributed.context import make_context                     # noqa: E402
from repro.launch import input_specs as IS                             # noqa: E402
from repro.launch.mesh import make_production_mesh                     # noqa: E402
from repro.models import model as M                                    # noqa: E402
from repro.training.optimizer import AdamWConfig                       # noqa: E402
from repro.training.train_step import make_train_step                  # noqa: E402

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
               "pred": 1, "s8": 1, "u8": 1, "f64": 8, "s64": 8, "u64": 8,
               "c64": 8, "s16": 2, "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"= ([^=\n]*?) "
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?(?:\.\d+)?\(")
_TYPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _bytes_of_types(text: str) -> int:
    total = 0
    for dt, dims in _TYPE_RE.findall(text):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum RESULT bytes of every collective instruction in the
    (per-device, post-SPMD) optimized HLO. Result size is the natural
    per-device traffic proxy: all-reduce result == operand size,
    all-gather result == the fully gathered tensor, all-to-all result
    == the exchanged buffer. ``-done`` ops carry no type and are
    skipped; ``-start`` tuple results count once."""
    out = {}
    for m in _COLL_RE.finditer(hlo_text):
        op = m.group(2)
        out[op] = out.get(op, 0) + _bytes_of_types(m.group(1))
    return out


def depth_variants(cfg):
    """Two reduced-DEPTH (same width/shape) variants for cost
    extrapolation, plus their depth-unit counts and the full count.

    XLA's cost_analysis counts a while-loop body once regardless of
    trip count, so the dry-run compiles two shallow fully-UNROLLED
    variants and extrapolates linearly — exact, since layers are
    identical. Units are 'groups' for heterogeneous stacks."""
    fam = cfg.family
    # base at 2/3 units, not 1/2: at depth 1 XLA sometimes picks a
    # different global collective strategy (observed: all-gather-heavy
    # L=1 prefill), which breaks the linear fit.
    if fam in ("dense", "moe"):
        return (dataclasses.replace(cfg, num_layers=2),
                dataclasses.replace(cfg, num_layers=3),
                2, 3, cfg.num_layers)
    if fam == "vlm":
        e = cfg.cross_attn_every
        return (dataclasses.replace(cfg, num_layers=2 * e),
                dataclasses.replace(cfg, num_layers=3 * e),
                2, 3, cfg.num_layers // e)
    if fam == "encdec":
        return (dataclasses.replace(cfg, num_layers=2, encoder_layers=2),
                dataclasses.replace(cfg, num_layers=3, encoder_layers=3),
                2, 3, cfg.num_layers)   # enc/dec stacks scale together
    if fam == "hybrid":
        e = cfg.ssm.shared_attn_every
        rem = cfg.num_layers % e
        return (dataclasses.replace(cfg, num_layers=2 * e + rem),
                dataclasses.replace(cfg, num_layers=3 * e + rem),
                2, 3, cfg.num_layers // e)
    if fam == "ssm":
        k = len(cfg.ssm.block_pattern or ("mlstm",))
        return (dataclasses.replace(cfg, num_layers=2 * k),
                dataclasses.replace(cfg, num_layers=3 * k),
                2, 3, cfg.num_layers // k)
    raise ValueError(fam)


def cost_one(cfg, shape, ctx) -> dict:
    """Compile one (possibly reduced-depth) variant with unrolled scans
    and return {flops, bytes, transcendentals, collectives}."""
    step, args, in_sh, out_sh = build_step(cfg, shape, ctx)
    mesh = ctx.mesh
    jitted = jax.jit(step, in_shardings=SH.to_named(in_sh, mesh),
                     out_shardings=SH.to_named(out_sh, mesh))
    M.SCAN_UNROLL = True
    try:
        compiled = jitted.lower(*args).compile()
    finally:
        M.SCAN_UNROLL = 1
    ca = compiled.cost_analysis() or {}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "transcendentals": float(ca.get("transcendentals", 0.0)),
            "collectives": collective_bytes(compiled.as_text())}


def extrapolated_cost(cfg, shape, ctx) -> dict:
    """Linear-in-depth extrapolation of per-device cost terms."""
    c1, c2, n1, n2, nf = depth_variants(cfg)
    v1 = cost_one(c1, shape, ctx)
    v2 = cost_one(c2, shape, ctx)

    def ext(a, b):
        return a + (b - a) * (nf - n1) / (n2 - n1)
    colls = {k: ext(v1["collectives"].get(k, 0), v2["collectives"].get(k, 0))
             for k in set(v1["collectives"]) | set(v2["collectives"])}
    return {"flops": ext(v1["flops"], v2["flops"]),
            "bytes": ext(v1["bytes"], v2["bytes"]),
            "transcendentals": ext(v1["transcendentals"],
                                   v2["transcendentals"]),
            "collectives": colls,
            "depth_units": [n1, n2, nf]}


def build_step(cfg, shape, ctx):
    """Returns (fn, kwargs_structs, in_shardings, out_shardings)."""
    cfg = IS.effective_config(cfg, shape)
    specs = IS.input_specs(cfg, shape)
    pspecs = SH.param_specs(specs["params"], ctx)
    if shape.kind == "train":
        step = make_train_step(cfg, AdamWConfig(), parallel=ctx,
                               remat="layer", sequence_parallel=True)
        ospecs = SH.opt_specs(specs["opt_state"], pspecs, ctx)
        bspecs = SH.batch_specs(specs["batch"], ctx)
        in_sh = (pspecs, ospecs, bspecs)
        metrics_sh = {k: jax.sharding.PartitionSpec() for k in
                      ("ce", "lb_loss", "loss", "grad_norm", "step")}
        out_sh = (pspecs, ospecs, metrics_sh)
        args = (specs["params"], specs["opt_state"], specs["batch"])
        return step, args, in_sh, out_sh
    if shape.kind == "prefill":
        def step(params, batch):
            return M.prefill(params, cfg, batch, parallel=ctx)
        bspecs = SH.batch_specs(specs["batch"], ctx)
        cache_shapes = jax.eval_shape(
            lambda: M.init_cache(cfg, shape.global_batch, shape.seq_len,
                                 frontend_len=cfg.frontend_tokens or None))
        cspecs = SH.cache_specs(cache_shapes, ctx, shape.global_batch)
        lspec = SH.logits_spec(ctx, shape.global_batch, cfg.vocab_size)
        in_sh = (pspecs, bspecs)
        out_sh = (lspec, cspecs)
        return step, (specs["params"], specs["batch"]), in_sh, out_sh
    # decode
    def step(params, token, cache, pos):
        return M.decode_step(params, cfg, token, cache, pos, parallel=ctx)
    cspecs = SH.cache_specs(specs["cache"], ctx, shape.global_batch)
    tok_spec = SH.batch_specs(specs["token"], ctx)
    lspec = SH.logits_spec(ctx, shape.global_batch, cfg.vocab_size)
    in_sh = (pspecs, tok_spec, cspecs, jax.sharding.PartitionSpec())
    out_sh = (lspec, cspecs)
    args = (specs["params"], specs["token"], specs["cache"], specs["pos"])
    return step, args, in_sh, out_sh


def _dpn(ctx):
    n = 1
    for a in ctx.data_axes:
        n *= ctx.mesh.shape[a]
    return n


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
            verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = make_context(mesh)
    t0 = time.time()
    record = {"arch": arch, "shape": shape_name,
              "mesh": "2x16x16" if multi_pod else "16x16",
              "n_devices": int(np.prod(list(mesh.shape.values())))}
    try:
        step, args, in_sh, out_sh = build_step(cfg, shape, ctx)
        in_named = SH.to_named(in_sh, mesh)
        out_named = SH.to_named(out_sh, mesh)
        jitted = jax.jit(step, in_shardings=in_named,
                         out_shardings=out_named)
        lowered = jitted.lower(*args)
        record["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t1, 1)
        ca = compiled.cost_analysis() or {}
        record["cost_analysis"] = {
            k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float)) and k in
            ("flops", "bytes accessed", "transcendentals",
             "utilization operand 0 {}", "bytes accessed output {}")}
        try:
            ma = compiled.memory_analysis()
            record["memory_analysis"] = {
                k: int(getattr(ma, k)) for k in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes")
                if hasattr(ma, k)}
        except Exception as e:  # CPU backend may not implement it
            record["memory_analysis"] = {"error": str(e)}
        hlo = compiled.as_text()
        record["collectives_rolled"] = collective_bytes(hlo)
        record["hlo_bytes"] = len(hlo)
        t2 = time.time()
        try:
            record["extrapolated"] = extrapolated_cost(
                IS.effective_config(cfg, shape), shape, ctx)
            record["costing_s"] = round(time.time() - t2, 1)
        except Exception as e:
            record["extrapolated"] = {"error": f"{type(e).__name__}: {e}"}
        record["status"] = "ok"
    except Exception as e:
        record["status"] = "fail"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-3000:]
    record["total_s"] = round(time.time() - t0, 1)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = f"{arch}__{shape_name}__{record['mesh']}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(record, f, indent=1)
    if verbose:
        ex = record.get("extrapolated", {})
        coll = ex.get("collectives", record.get("collectives_rolled", {}))
        print(f"[{record['status']:4s}] {arch:26s} {shape_name:12s} "
              f"{record['mesh']:8s} lower={record.get('lower_s', 0):6.1f}s "
              f"compile={record.get('compile_s', 0):6.1f}s "
              f"GFLOP/dev={ex.get('flops', 0) / 1e9:10.1f} "
              f"coll={sum(coll.values()) / 1e6:8.1f}MB",
              flush=True)
        if record["status"] == "fail":
            print(record["error"], flush=True)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="all (arch x shape) on the selected mesh")
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    ap.add_argument("--baseline", action="store_true",
                    help="use the pre-hillclimb sharding choices")
    args = ap.parse_args()
    if args.baseline:
        SH.set_baseline()
    archs = [args.arch] if args.arch else \
        [a for a in list_configs() if a != "llama3-70b"]
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    n_fail = 0
    for arch in archs:
        for shape in shapes:
            rec = run_one(arch, shape, args.multi_pod, args.out)
            n_fail += rec["status"] != "ok"
    print(f"dry-run complete: {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
