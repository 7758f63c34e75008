"""The benchmark's harness: build the served fleet for one cell, warm
it, drive it with open-loop HTTP traffic, measure a window, and check
what the window served against the plain reference.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by its name: ``configs/<config>.json``, the
model module that configuration names, ``models/<model>.py`` (weights,
reference, program config, counts and kernel names: ``MODEL_API``),
``traffic/<mix>.json``, ``metrics/<metric>.py`` (a ``read(record)``
that returns a number, or None where it finds nothing to read).
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import importlib.util
import json
import math
import os
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

import numpy as np  # noqa: E402

from chipbench import client as C  # noqa: E402
from chipbench import reference as R  # noqa: E402
from chipbench import stats as S  # noqa: E402
from chipbench import trace as TR  # noqa: E402
from chipbench import traffic as T  # noqa: E402

# The traced part of a --trace 1 window: it starts this share of the
# window in and lasts TRACE_S seconds (or this share of a short window).
TRACE_START, TRACE_S, TRACE_SHARE = 0.3, 4.0, 0.4
# Reference check: requests are drawn until they cover the
# configuration's ``min_compared_tokens``, at most this many. Azure
# traffic averages ~46 served tokens a request, but 58 of its 96 ask for
# 8, so 32 draws could fall short of 600; 64 reach it.
CHECK_REQUESTS = 64
# Where a configuration's "model" is found, and what its module gives.
MODELS = HERE / "models"
MODEL_API = ("KERNELS", "program_config", "program_params", "layer_params",
             "top_params", "Forward", "prefill_flops", "decode_flops",
             "decode_kernel_cost")


# ------------------------------------------------------------ discovery
def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def load_config(name: str) -> dict:
    path = HERE / "configs" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration {name!r} at {path}")
    return json.loads(path.read_text())


def load_model(cfgj: dict):
    """The model module that a configuration names by its "model" key:
    ``MODELS/<model>.py``, with every name of ``MODEL_API``."""
    if "model" not in cfgj:
        raise KeyError(f"configuration {cfgj.get('name')!r} has no "
                       f"\"model\" key naming its module in {MODELS}")
    path = MODELS / f"{cfgj['model']}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no model module {cfgj['model']!r} at "
                                f"{path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_model_" + cfgj["model"], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [n for n in MODEL_API if not hasattr(mod, n)]
    if missing:
        raise AttributeError(f"model module {path} lacks "
                             f"{', '.join(missing)}")
    return mod


def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise LookupError(f"device kind {kind!r} is not in peaks.json; "
                          f"known: {[k for k in table if k[0] != '_']}")
    return table[kind]


def metrics_for(bench: dict, cell: str, section: str) -> List[dict]:
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


# ------------------------------------------------------------ recording
@dataclasses.dataclass
class Record:
    """What one run leaves for the per-layer readers. ``model`` is the
    configuration's model module (``load_model``), loaded once a run."""
    cfg: dict
    peak: dict
    model: Any = None
    window_s: float = 0.0
    counters: Dict[str, dict] = dataclasses.field(default_factory=dict)
    spans: List[dict] = dataclasses.field(default_factory=list)
    trace: Optional[dict] = None
    trace_t0: Optional[float] = None
    trace_t1: Optional[float] = None
    served: Dict[int, dict] = dataclasses.field(default_factory=dict)
    spans_on: bool = False


def install(rt, gw, rec: Record, trace: bool) -> None:
    """Wrap the layer calls. The submit wrapper always keeps what the
    engine was given (for the check); the span wrappers exist only in a
    traced run."""
    import jax

    orig_submit = rt.submit

    def submit(req):
        if trace:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("gateway.submit"):
                d = orig_submit(req)
            if rec.spans_on:
                rec.spans.append({"name": "gateway.submit", "t0": t0,
                                  "t1": time.perf_counter()})
        else:
            d = orig_submit(req)
        eng = rt.engines[d.pool]
        sreq = next(r for r in reversed(eng.waiting) if r.rid == req.rid)
        rec.served[req.rid] = {
            "pool": d.pool, "compressed": bool(d.compressed),
            "text": d.compressed_text if d.compressed else None,
            "ids": np.asarray(sreq.tokens, np.int32),
            "max_new": sreq.max_new_tokens}
        return d
    rt.submit = submit
    if not trace:
        return
    for name, eng in rt.engines.items():
        _wrap_engine(name, eng, rec)
    orig_flush = gw._flush

    def flush():
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("gateway.flush"):
            orig_flush()
        if rec.spans_on:
            rec.spans.append({"name": "gateway.flush", "t0": t0,
                              "t1": time.perf_counter()})
    gw._flush = flush


def _wrap_engine(pool: str, eng, rec: Record) -> None:
    import jax
    cur = {"prefill": [], "decode": []}
    orig_step = eng.step
    orig_adv = eng._advance_prefill_host
    orig_app = eng._append_token

    def advance(chunks):
        for s, chunk in chunks.items():
            cur["prefill"].append((int(eng.slot_pos[s]), len(chunk)))
        return orig_adv(chunks)

    def append(s, tok):
        cur["decode"].append(int(eng.slot_pos[s]))
        return orig_app(s, tok)

    def step():
        cur["prefill"], cur["decode"] = [], []
        d0, dd0 = eng.dispatches, eng.decode_dispatches
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"step.{pool}"):
            orig_step()
        t1 = time.perf_counter()
        if eng.dispatches == d0:
            kind = "idle"
        elif eng.decode_dispatches > dd0:
            kind = "decode"
        else:
            kind = "mixed" if cur["decode"] else "prefill"
        if rec.spans_on:
            rec.spans.append({"name": f"step.{pool}", "pool": pool,
                              "t0": t0, "t1": t1, "kind": kind,
                              "prefill": list(cur["prefill"]),
                              "decode": list(cur["decode"])})
    eng._advance_prefill_host = advance
    eng._append_token = append
    eng.step = step


def counters(rt) -> dict:
    s = rt.router.stats
    out = {"router": {"total": s.total, "to_short": s.to_short,
                      "to_long": s.to_long, "borderline": s.borderline,
                      "compressed_ok": s.compressed_ok,
                      "compression_attempts": s.compression_attempts,
                      "compression_ms_sum": s.compression_ms_sum}}
    for name, e in rt.engines.items():
        out[name] = {"dispatches": e.dispatches,
                     "decode_dispatches": e.decode_dispatches,
                     "decode_tokens": e.decode_tokens_emitted,
                     "occ_slot_iters": e._occ_slot_iters,
                     "iteration": e.iteration,
                     "waiting": len(e.waiting),
                     "busy_slots": sum(r is not None for r in e.slot_req)}
    return out


def delta(a: dict, b: dict) -> dict:
    return {k: {f: b[k][f] - a[k][f] for f in a[k]} for k in a}


class CompileCounter:
    """Backend compiles, from JAX's monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.events: List[tuple] = []
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), duration,
                                kw.get("fun_name", "?")))

    def between(self, t0: float, t1: float) -> List[tuple]:
        return [e for e in self.events if t0 <= e[0] < t1]


# --------------------------------------------------------------- build
def build(cfgj: dict, mix: dict, seed: int, model):
    """Plan the fleet as ``repro.launch.serve.build_fleet_runtime``
    does (k=2 pools), with the weights of the configuration's model
    module ``model`` and each pool's KV held at ``num_blocks``."""
    import jax
    from repro.core.planner import plan_k_pool
    from repro.core.workload import get_workload
    from repro.serving.config import ServingConfig
    from repro.serving.pools import FleetRuntime
    sv = cfgj["serving"]
    plan = plan_k_pool(get_workload(mix["plan_workload"]), lam=1000.0,
                       t_slo=0.5, k=sv["pools"])
    scale = sv["top_context"] / plan.pools[-1].c_max
    if not math.isclose(scale, mix["scale"]):
        raise ValueError(f"mix scale {mix['scale']} != top context "
                         f"{sv['top_context']} / plan top "
                         f"{plan.pools[-1].c_max}")
    params = model.program_params(cfgj, seed)
    jax.block_until_ready(params)
    scfg = ServingConfig(paged=True, decode_k=sv["decode_k"],
                         decode_impl=sv["decode_impl"],
                         block_size=sv["block_size"],
                         num_blocks=sv.get("num_blocks"))
    rt = FleetRuntime.from_plan(model.program_config(cfgj), params, plan,
                                slots_per_pool=sv["slots"],
                                c_chunk=sv["c_chunk"], ctx_scale=scale,
                                config=scfg)
    return rt, plan


def warm(rt) -> None:
    """Compile (or load) every program the window will run, for both
    pools, with inputs that change nothing: each prefill bucket alone
    and fused with decode, and the decode scan, all with no live row."""
    import jax
    for eng in rt.engines.values():
        n = eng.n_max
        zeros = np.zeros(n, np.int32)
        none = np.zeros(n, bool)
        for b in eng.buckets:
            tok = np.zeros((n, b), np.int32)
            eng.cache = eng._prefill_step(
                eng.params, eng.cache, eng._upload(tok),
                eng._block_table_device(), eng._upload(zeros.copy()),
                eng._upload(zeros.copy()))
            nxt, eng.cache = eng._mixed(
                eng.params, eng.cache, eng._upload(tok),
                eng._block_table_device(), eng._upload(zeros.copy()),
                eng._upload(zeros.copy()),
                eng._upload(np.zeros((n, 1), np.int32)),
                eng._upload(none.copy()))
            np.asarray(nxt)
        eng._dev_dirty = True
        tok, pos, active, budget = eng._device_decode_state(none.copy())
        carry, emitted = eng._decode_scan(
            eng.params, eng.cache, tok, eng._block_table_device(), pos,
            active, budget)
        eng.cache = carry[0]
        np.asarray(emitted)
        eng._dev_state, eng._dev_dirty = None, True
        jax.block_until_ready(eng.cache)


# ---------------------------------------------------------------- drive
async def _until(t: float) -> None:
    d = t - time.perf_counter()
    if d > 0:
        await asyncio.sleep(d)


async def drive(rt, reqs: List[T.Req], seconds: float, warmup_s: float,
                follow_s: float, rec: Record, trace: bool,
                compiles: CompileCounter, trace_dir: Optional[str]) -> dict:
    """Serve the schedule through the HTTP gateway; returns timings."""
    import jax
    from repro.serving.server import ServingGateway
    gw = ServingGateway(rt, port=0, request_timeout_s=900.0)
    install(rt, gw, rec, trace)
    await gw.start()
    t0 = time.perf_counter() + 0.05
    results = [C.Result(r, t0 + r.due) for r in reqs]
    tasks = [asyncio.ensure_future(C.send(gw.host, gw.port, res))
             for res in results]
    w0, w1 = t0 + warmup_s, t0 + warmup_s + seconds
    out = {"t0": t0, "w0": w0, "w1": w1, "results": results}
    try:
        await _until(w0)
        c0 = counters(rt)
        rec.spans_on = True
        if trace:
            length = min(TRACE_S, TRACE_SHARE * seconds)
            await _until(w0 + TRACE_START * seconds)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            async with gw._lock:
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                ann = jax.profiler.TraceAnnotation(TR.WINDOW)
                ann.__enter__()
                rec.trace_t0 = time.perf_counter()
            await asyncio.sleep(length)
            async with gw._lock:
                ann.__exit__(None, None, None)
                rec.trace_t1 = time.perf_counter()
            await asyncio.get_running_loop().run_in_executor(
                None, jax.profiler.stop_trace)
        await _until(w1)
        rec.spans_on = False
        c1 = counters(rt)
        rec.counters = {"window": delta(c0, c1), "start": c0, "end": c1}
        out["compiles_in_window"] = compiles.between(w0, w1)
        # follow requests due in the window to their first token, for
        # the mix's follow_s seconds (0 in a cell above the knee, where
        # the queue grows through the run)
        due = [r for r in results if r.req.in_window]
        deadline = w1 + follow_s
        while time.perf_counter() < deadline and any(
                r.first is None and r.error is None and r.done is None
                for r in due):
            await asyncio.sleep(0.05)
        out["t_end"] = time.perf_counter()
    finally:
        await shutdown(gw, tasks)
    return out


async def shutdown(gw, tasks) -> None:
    """Stop the clients, then every other task on the loop (the
    gateway's per-connection handlers wait for tokens of requests still
    in flight, and Python 3.12's ``Server.wait_closed`` waits for
    them), then the gateway."""
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    me = asyncio.current_task()
    rest = [t for t in asyncio.all_tasks() if t is not me]
    for t in rest:
        t.cancel()
    await asyncio.gather(*rest, return_exceptions=True)
    await gw.stop()


# ------------------------------------------------------------- reduce
def end_to_end(out: dict, seconds: float, follow_s: float) -> dict:
    """Client-side numbers of the window. A request due in the window
    failed if it was refused or errored, or, where the mix follows
    requests past the close, gave no first token by then."""
    w0, w1 = out["w0"], out["w1"]
    res = out["results"]
    due = [r for r in res if r.req.in_window]
    ttft = [(r.first - r.due) * 1e3 for r in due if r.first is not None]
    failed = [r for r in due if r.error is not None
              or (follow_s > 0 and r.first is None)]
    tpots = [t for t in (S.tpot_s(r.chunks, w0, w1) for r in res)
             if t is not None]
    toks = sum(S.tokens_in_window(r.chunks, w0, w1) for r in res)
    late = [r.sent - r.due for r in res if r.sent is not None]
    return {"attempted": len(due), "failed": len(failed),
            "unserved": sum(r.first is None for r in due),
            "errors": [r.error for r in failed if r.error][:5],
            "ttft_ms": ttft, "tpot_ms": [t * 1e3 for t in tpots],
            "output_tok_s": toks / seconds, "output_tokens": toks,
            "late_s": late}


def per_layer(rec: Record, names: List[str]) -> Dict[str, float]:
    out = {}
    for n in names:
        v = load_reader(n)(rec)
        if v is not None:
            out[n] = v
    return out


# -------------------------------------------------------------- check
def check(cfgj: dict, seed: int, out: dict, rec: Record, rt_shape: dict,
          control: bool = False) -> dict:
    """Compare what the window served with the plain reference, the
    ``Forward`` of ``rec.model``. Returns {name: (value, limit)} plus
    details."""
    limits = cfgj["check"]
    vocab = cfgj["vocab_size"]
    b, gamma = rt_shape["boundary"], rt_shape["gamma"]
    by_rid = {r.rid: r for r in out["results"] if r.rid is not None}
    route_bad = comp_bad = prompt_bad = 0
    for rid, sv in rec.served.items():
        r = by_rid.get(rid)
        if r is None:
            continue
        q = r.req
        allowed = R.expected_pools(q.prompt, q.l_out, q.category, b, gamma,
                                   margin=max(4, math.ceil(0.005 * b)))
        if (sv["pool"], sv["compressed"]) not in allowed:
            route_bad += 1
        if sv["compressed"]:
            faults = R.compression_faults(q.prompt, sv["text"], sv["ids"],
                                          b - q.l_out, vocab)
            comp_bad += bool(faults)
        elif list(sv["ids"]) != R.tokenize(q.prompt, vocab):
            prompt_bad += 1
    # served requests: finished ones, and per pool the longest partial
    # one where a pool finished none
    done, partial = {}, {}
    for rid, r in by_rid.items():
        sv = rec.served.get(rid)
        if sv is None or not r.tokens:
            continue
        item = {"prompt_ids": sv["ids"], "served": list(r.tokens),
                "pool": sv["pool"], "compressed": sv["compressed"]}
        (done if r.done is not None else partial)[rid] = item
    for pool in {v["pool"] for v in partial.values()} - \
            {v["pool"] for v in done.values()}:
        cand = {k: v for k, v in partial.items() if v["pool"] == pool}
        k = max(cand, key=lambda k: len(cand[k]["served"]))
        done[k] = cand[k]
    pick = R.sample_requests(done, seed, limits["min_compared_tokens"],
                             CHECK_REQUESTS)
    ref = rec.model.Forward(cfgj, seed)
    ctl = rec.model.Forward(cfgj, seed, int8=True) if control else None
    gap_sum, ctl_sum, compared = 0.0, 0.0, 0
    per_req = []
    for rid in pick:
        it = done[rid]
        toks, first = R.served_sequence(it["prompt_ids"], it["served"])
        lg = ref.logits(toks, first)
        g = R.gaps(lg, it["served"])
        gap_sum += float(g.sum())
        compared += len(g)
        row = {"rid": rid, "pool": it["pool"],
               "compressed": it["compressed"],
               "prompt": len(it["prompt_ids"]), "served": len(g),
               "gap_max": float(g.max()), "gap_mean": float(g.mean()),
               "logit_max_abs": float(np.abs(lg).max())}
        if ctl is not None:
            cl = ctl.logits(toks, first)
            gc_ = R.gaps(lg, cl.argmax(-1))
            ctl_sum += float(gc_.sum())
            row["control_gap_max"] = float(gc_.max())
            row["control_gap_mean"] = float(gc_.mean())
        per_req.append(row)

    def verdict(gap_sum: float):
        # the mean, over every compared token, of how far the served
        # token's reference logit lies below the reference's best. The
        # widest such gap is set by a few near-ties of the top two
        # logits, which bf16 and int8 rounding both flip, so it does
        # not tell the int8 control from the program; the mean counts
        # every flip (PERF.md, section 2)
        mean = gap_sum / max(compared, 1)
        checks = {
            "logit_gap_mean": (mean, limits["logit_gap_mean"]),
            "route_faults": (route_bad, 0),
            "compress_faults": (comp_bad, 0),
            "prompt_faults": (prompt_bad, 0),
            "compared_tokens": (compared, limits["min_compared_tokens"]),
        }
        ok = (mean <= limits["logit_gap_mean"] and route_bad == 0
              and comp_bad == 0 and prompt_bad == 0
              and compared >= limits["min_compared_tokens"])
        return ok, checks

    ok, checks = verdict(gap_sum)
    res = {"ok": ok, "checks": checks, "requests": per_req}
    if control:
        # the control in the program's place, judged by the same test
        res["control_ok"], res["control_checks"] = verdict(ctl_sum)
    return res


# ------------------------------------------------------------------ run
def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             bench: dict, cfgj: Optional[dict] = None,
             mix: Optional[dict] = None, t_start: Optional[float] = None,
             control: bool = False, log=print) -> dict:
    """One run of one cell, in this process. Returns the result line's
    content and the details printed before it."""
    import jax
    t_start = t_start if t_start is not None else time.perf_counter()
    cfgj = cfgj or load_config(cell["config"])
    mix = mix or T.load(cell["traffic"])
    dev = jax.devices()[0]
    peak = peaks_for(dev.device_kind) if dev.platform != "cpu" else \
        {"bf16_flops_per_s": float("nan"), "hbm_bytes_per_s": float("nan")}
    compiles = CompileCounter()
    reqs = T.schedule(mix, seed, seconds)
    model = load_model(cfgj)
    rt, plan = build(cfgj, mix, seed, model)
    warm(rt)
    rec = Record(cfg=cfgj, peak=peak, model=model)
    boundary = rt.router.boundaries[0]
    gamma = rt.router.gammas[0]
    log(f"fleet: boundary {boundary} gamma {gamma} pools "
        + ", ".join(f"{n}: {e.n_max} slots x {e.c_max} tokens, "
                    f"{e.num_blocks} blocks"
                    for n, e in rt.engines.items()))
    log(f"schedule: {len(reqs)} requests, "
        f"{sum(r.in_window for r in reqs)} due in the window; bands "
        f"{T.bands([r for r in reqs if r.in_window], boundary, gamma)}")
    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tdir:
        out = asyncio.run(drive(rt, reqs, seconds, float(mix["warmup_s"]),
                                float(mix["follow_s"]), rec, trace,
                                compiles, tdir))
        setup_s = out["t0"] - t_start
        rec.window_s = seconds
        if trace:
            rec.trace = TR.reduce(TR.load(tdir).planes, model.KERNELS)
            if os.environ.get("CHIPBENCH_DESCRIBE_TRACE"):
                for line in TR.describe(TR.load(tdir).planes):
                    log("trace: " + line)
    e2e = end_to_end(out, seconds, float(mix["follow_s"]))
    stats = jax.devices()[0].memory_stats() or {}
    mem_peak = stats.get("peak_bytes_in_use")
    rt_shape = {"boundary": boundary, "gamma": gamma}
    routed = rec.counters["window"]["router"]
    # free the program before the reference runs
    rt.engines.clear()
    del rt
    gc.collect()
    t_check = time.perf_counter()
    chk = check(cfgj, seed, out, rec, rt_shape, control=control)
    check_s = time.perf_counter() - t_check
    return {"setup_s": setup_s, "e2e": e2e, "rec": rec, "out": out,
            "check": chk, "check_s": check_s, "mem_peak": mem_peak,
            "routed": routed, "device": dev, "peak": peak,
            "compiles": compiles}
