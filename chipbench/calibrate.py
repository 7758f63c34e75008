#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python chipbench/calibrate.py --workload minitron8b.agent \
        --seeds 11,12,13 --seconds 10

For each seed, in this one process: a run of the cell as the benchmark
makes it (its own load, a short window), then the check, which reads
the mean logit gap of the served tokens against the float32 reference
(the sound reading) and, at the same positions of the same requests,
the mean gap of the tokens that the reference computed with int8
inputs puts first (the control); the widest gaps of both beside them. Prints one JSON line per seed. Not
part of a benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if sys.path and Path(sys.path[0]).resolve() == HERE:
    sys.path.pop(0)
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    import jax
    from chipbench import harness as H
    from chipbench import run
    if jax.devices()[0].platform == "cpu":
        raise SystemExit("calibrate.py needs the chip")
    run.compile_cache()
    bench = H.load_benchmark()
    cell = H.find_cell(bench, args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = H.run_cell(cell, seed, args.seconds, False, bench,
                         control=True, log=lambda m: None)
        chk = res["check"]
        rows = chk["requests"]
        print(json.dumps({
            "seed": seed, "ok": chk["ok"],
            "logit_gap_mean": chk["checks"]["logit_gap_mean"][0],
            "control_gap_mean": chk["control_checks"]["logit_gap_mean"][0],
            "gap_max": max((r["gap_max"] for r in rows), default=None),
            "control_gap_max": max((r["control_gap_max"] for r in rows),
                                   default=None),
            "control_ok": chk["control_ok"],
            "checks": {k: v[0] for k, v in chk["checks"].items()},
            "requests": chk["requests"], "setup_s": res["setup_s"],
            "check_s": res["check_s"]}), flush=True)
        del res
        gc.collect()


if __name__ == "__main__":
    main()
