#!/usr/bin/env python3
"""FleetOpt served-path benchmark: one cell, one run.

    python chipbench/run.py --workload minitron8b.agent --seed 7 \
        --seconds 40 --trace 0

One process holds the chip, builds the cell's two-pool fleet (FleetOpt
plan -> C&R gateway router -> paged engines with Pallas decode) behind
the HTTP ``ServingGateway``, warms every program the window runs, sends
the cell's open-loop traffic for its warm-up seconds, then measures
``--seconds``. ``--trace 0`` reports the end-to-end metrics,
``--trace 1`` the per-layer ones (with a profiler trace of a few steady
seconds). Then the program is freed and what the window served is
checked against the plain reference: the ``Forward`` of the model module
that the cell's configuration names (``models/<model>.py``, built from
``reference.py``'s blocks).

The last line of standard output is the result as JSON. With no
accelerator, fewer chips than the cell asks for, or a device kind not
in ``peaks.json``, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# run as a script: import the benchmark as the package ``chipbench``
# (its module names would otherwise shadow top-level ones) and the
# program from src/
if sys.path and Path(sys.path[0]).resolve() == HERE:
    sys.path.pop(0)
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str, code: int = 2) -> None:
    print(msg, file=sys.stderr, flush=True)
    raise SystemExit(code)


def compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def result_line(cell: dict, bench: dict, res: dict, trace: bool,
                seconds: float) -> dict:
    from chipbench import harness as H
    from chipbench import stats as S
    e2e, chk = res["e2e"], res["check"]
    metrics = {}
    if not trace:
        values = {"setup_s": res["setup_s"],
                  "output_tok_s": e2e["output_tok_s"]}
        if e2e["ttft_ms"]:
            values["ttft_p50_ms"] = S.percentile(e2e["ttft_ms"], 50)
            values["ttft_p95_ms"] = S.percentile(e2e["ttft_ms"], 95)
        if e2e["tpot_ms"]:
            values["tpot_p50_ms"] = statistics.median(e2e["tpot_ms"])
        for m in H.metrics_for(bench, cell["name"], "end_to_end"):
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        wanted = H.metrics_for(bench, cell["name"], "per_layer")
        got = H.per_layer(res["rec"], [m["name"] for m in wanted])
        for m in wanted:
            if m["name"] in got:
                metrics[m["name"]] = {"value": got[m["name"]],
                                      "unit": m["unit"]}
    dev = res["device"]
    import jax
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": res["mem_peak"]}
    line = {"correct": bool(chk["ok"]), "attempted": e2e["attempted"],
            "failed": e2e["failed"], "metrics": metrics, "device": device}
    tr = res["rec"].trace
    if trace and tr:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in chk["checks"].items()}
    return line


def report(res: dict, seconds: float) -> None:
    """Earlier lines: what the result line does not carry."""
    from chipbench import stats as S
    e2e, out = res["e2e"], res["out"]
    log(f"setup_s {res['setup_s']:.3f}; requests due in the window "
        f"{e2e['attempted']}, failed {e2e['failed']}, without a first "
        f"token at the end {e2e['unserved']}")
    late = e2e["late_s"]
    if late:
        log(f"client lateness: median {statistics.median(late) * 1e3:.3f} "
            f"ms, max {max(late) * 1e3:.3f} ms over {len(late)} sends")
    cw = out.get("compiles_in_window", [])
    log(f"compiles inside the window: {len(cw)}"
        + ("" if not cw else " (" + ", ".join(
            f"{n} {d:.2f}s" for _, d, n in cw[:8]) + ")"))
    r = res["routed"]
    tot = max(1, r["total"])
    log(f"routed in the window: {r['total']} requests; short "
        f"{r['to_short'] / tot:.4f} (compressed "
        f"{r['compressed_ok'] / tot:.4f}), long {r['to_long'] / tot:.4f}; "
        f"borderline {r['borderline']}, C&R attempts "
        f"{r['compression_attempts']}")
    st, en = res["rec"].counters["start"], res["rec"].counters["end"]
    for pool in (k for k in st if k != "router"):
        log(f"pool {pool}: busy slots {st[pool]['busy_slots']} at the "
            f"window's open, {en[pool]['busy_slots']} at its close; "
            f"waiting {st[pool]['waiting']} -> {en[pool]['waiting']}")
    n = len(e2e["ttft_ms"])
    log(f"first tokens in the window: {n}; ttft p95 needs "
        f"{S.min_samples(95)} (ten beyond it)"
        + (f"; p50 {S.percentile(e2e['ttft_ms'], 50):.3f} ms, p95 "
           f"{S.percentile(e2e['ttft_ms'], 95):.3f} ms" if n else ""))
    log(f"tpot samples: {len(e2e['tpot_ms'])}; output tokens in window "
        f"{e2e['output_tokens']} over {seconds} s")
    if e2e["errors"]:
        log(f"errors: {e2e['errors']}")
    log(f"memory_stats peak_bytes_in_use after the window: "
        f"{res['mem_peak']}")
    c = res["check"]
    for row in c["requests"]:
        log(f"checked request {row}")
    log(f"reference check took {res['check_s']:.1f} s")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness as H
    bench = H.load_benchmark()
    cell = H.find_cell(bench, args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        fail("no accelerator: JAX found only the CPU")
    if len(devs) < cell["chips"]:
        fail(f"the cell needs {cell['chips']} chips, JAX found {len(devs)}")
    try:
        H.peaks_for(devs[0].device_kind)
    except LookupError as e:
        fail(str(e))
    cache = compile_cache()
    log(f"device {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"jax {jax.__version__}; compile cache {cache}")
    res = H.run_cell(cell, args.seed, args.seconds, bool(args.trace), bench,
                     t_start=T_START, log=log)
    report(res, args.seconds)
    line = result_line(cell, bench, res, bool(args.trace), args.seconds)
    for k, v in line["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
