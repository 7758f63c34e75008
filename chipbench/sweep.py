#!/usr/bin/env python3
"""Find a cell's knee once, on the chip: the highest offered rate at
which the engines' waiting queues do not grow over a window.

    python chipbench/sweep.py --config minitron-8b --traffic azure \
        --rates 1,2,3,4 --seconds 30 --seed 3

Builds the cell's weights once; for each rate builds a fresh fleet on
them (so no backlog carries over), sends the cell's traffic at that
rate for its warm-up and a window, and samples the waiting queues every
half second of the window. Prints one JSON line per rate. Not part of a
benchmark run.
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import json
import statistics
import sys
import time
from pathlib import Path

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


async def one_rate(rt, reqs, warmup_s, seconds):
    from chipbench import client as C
    from chipbench import harness as H
    from chipbench import stats as S
    from repro.serving.server import ServingGateway
    gw = ServingGateway(rt, port=0, request_timeout_s=900.0)
    await gw.start()
    t0 = time.perf_counter() + 0.05
    results = [C.Result(r, t0 + r.due) for r in reqs]
    tasks = [asyncio.ensure_future(C.send(gw.host, gw.port, x))
             for x in results]
    w0, w1 = t0 + warmup_s, t0 + warmup_s + seconds
    samples = []
    try:
        while time.perf_counter() < w0:
            await asyncio.sleep(0.1)
        while time.perf_counter() < w1:
            samples.append((time.perf_counter() - w0,
                            sum(len(e.waiting)
                                for e in rt.engines.values()),
                            sum(r is not None for e in rt.engines.values()
                                for r in e.slot_req)))
            await asyncio.sleep(0.5)
    finally:
        await H.shutdown(gw, tasks)
    due = [x for x in results if x.req.in_window]
    ttft = [(x.first - x.due) * 1e3 for x in due if x.first is not None]
    half = w0 + seconds / 2
    first_half = [(x.first - x.due) * 1e3 for x in due
                  if x.first is not None and x.due < half]
    second_half = [(x.first - x.due) * 1e3 for x in due
                   if x.first is not None and x.due >= half]
    ts = [t for t, _, _ in samples]
    qs = [q for _, q, _ in samples]
    busy = [b for _, _, b in samples]
    slope = statistics.linear_regression(ts, qs).slope if len(ts) > 2 \
        else float("nan")
    toks = sum(S.tokens_in_window(x.chunks, w0, w1) for x in results)
    return {"due": len(due), "first_tokens": len(ttft),
            "ttft_p50_ms": statistics.median(ttft) if ttft else None,
            "ttft_p50_first_half": statistics.median(first_half)
            if first_half else None,
            "ttft_p50_second_half": statistics.median(second_half)
            if second_half else None,
            "queue_start": qs[0] if qs else None,
            "queue_end": qs[-1] if qs else None,
            "queue_max": max(qs) if qs else None,
            "queue_slope_per_s": slope,
            "busy_slots_mean": statistics.fmean(busy) if busy else None,
            "output_tok_s": toks / seconds}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--warmup", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=3)
    args = ap.parse_args(argv)
    import jax
    from chipbench import harness as H
    from chipbench import run
    from chipbench import traffic as T
    from repro.core.planner import plan_k_pool
    from repro.core.workload import get_workload
    from repro.serving.config import ServingConfig
    from repro.serving.pools import FleetRuntime
    if jax.devices()[0].platform == "cpu":
        raise SystemExit("sweep.py needs the chip")
    run.compile_cache()
    cfgj = H.load_config(args.config)
    mix = T.load(args.traffic)
    sv = cfgj["serving"]
    model = H.load_model(cfgj)
    params = model.program_params(cfgj, args.seed)
    plan = plan_k_pool(get_workload(mix["plan_workload"]), lam=1000.0,
                       t_slo=0.5, k=sv["pools"])
    for rate in [float(r) for r in args.rates.split(",")]:
        mix_r = dict(mix, rate_per_s=rate, warmup_s=args.warmup)
        reqs = T.schedule(mix_r, args.seed, args.seconds)
        scfg = ServingConfig(paged=True, decode_k=sv["decode_k"],
                             decode_impl=sv["decode_impl"],
                             block_size=sv["block_size"],
                             num_blocks=sv.get("num_blocks"))
        t = time.perf_counter()
        rt = FleetRuntime.from_plan(
            model.program_config(cfgj), params, plan,
            slots_per_pool=sv["slots"], c_chunk=sv["c_chunk"],
            ctx_scale=mix["scale"], config=scfg)
        H.warm(rt)
        built = time.perf_counter() - t
        out = asyncio.run(one_rate(rt, reqs, args.warmup,
                                   args.seconds))
        out.update(rate=rate, build_s=built)
        print(json.dumps(out), flush=True)
        rt.engines.clear()
        del rt
        gc.collect()


if __name__ == "__main__":
    main()
