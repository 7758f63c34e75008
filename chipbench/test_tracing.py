"""The program's own spans and counters (``repro.serving.tracing``, the
engine's queue-wait and KV block counters) and what the benchmark is to
read from them once its harness keeps them: the four readers and the
idle split by program span (``program_trace.py``). Everything runs on
the CPU at a 2-layer model."""
import asyncio
import dataclasses
import json
import time
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

from chipbench import harness as H
from chipbench import program_trace as PT
from chipbench import trace as TR
from repro.configs.base import get_config
from repro.models import model as M
from repro.serving import tracing
from repro.serving.config import ServingConfig
from repro.serving.engine import InferenceEngine, ServeRequest
from repro.serving.metrics import fleet_metrics, render_prometheus
from repro.serving.pools import FleetRuntime
from repro.serving.server import ServingGateway
from repro.serving.tracing import Span

MS = 1_000_000


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(get_config("minitron-8b").reduced(),
                              dtype="float32")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def engine(model, **kw):
    cfg, params = model
    kw = dict(dict(n_max=2, c_max=64, c_chunk=16, paged=True, block_size=16,
                   decode_k=4), **kw)
    return InferenceEngine(cfg, params, kw.pop("n_max"), kw.pop("c_max"),
                           **kw)


def submit(eng, n, prompt=20, new=6, seed=0):
    rng = np.random.default_rng(seed)
    for rid in range(n):
        eng.submit(ServeRequest(
            rid=rid, tokens=[int(t) for t in rng.integers(1, 900, prompt)],
            max_new_tokens=new))


# ------------------------------------------------------------ (a) off
def test_off_records_nothing_and_builds_no_annotation(model, monkeypatch):
    built = []
    monkeypatch.setattr(tracing, "TraceAnnotation",
                        lambda name: built.append(name))
    eng = engine(model)
    assert not eng.tracer.on
    assert eng.tracer.span("eng.step") is eng.tracer.span("gw.pass")
    submit(eng, 3)
    eng.run_to_completion(200)
    assert len(eng.results) == 3
    assert eng.tracer.spans == [] and built == []


# --------------------------------------------- (b) nesting, one request
@pytest.fixture(scope="module")
def gateway_spans(model):
    """One streamed completion through the HTTP gateway, tracer on."""
    cfg, params = model
    rt = FleetRuntime(cfg, params, boundaries=(64,), gammas=(1.4,),
                      n_maxes=(2, 2), c_maxes=(128, 256), c_chunk=16,
                      config=ServingConfig(paged=True, decode_k=4))

    async def main():
        gw = ServingGateway(rt, port=0)
        await gw.start()
        rt.tracer.start()
        try:
            body = json.dumps({"prompt": "a short prompt " * 6,
                               "max_tokens": 9, "stream": True}).encode()
            reader, writer = await asyncio.open_connection(gw.host, gw.port)
            writer.write(b"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
                         b"Content-Length: " + str(len(body)).encode()
                         + b"\r\n\r\n" + body)
            await writer.drain()
            raw = await asyncio.wait_for(reader.read(), timeout=120.0)
            writer.close()
            return raw
        finally:
            rt.tracer.stop()
            await gw.stop()

    raw = asyncio.run(main())
    assert b"data: [DONE]" in raw
    return rt.tracer.take()


@pytest.mark.parametrize("child,parents", [
    ("gw.handle", {None}),
    ("router.route", {"gw.handle"}),
    ("gw.lock_wait", {"gw.pass", "gw.handle"}),
    ("gw.hop", {"gw.pass"}),
    ("eng.step", {"gw.pass"}),
    ("eng.admit", {"eng.step"}),
    ("eng.alloc", {"eng.step"}),
    ("eng.dispatch", {"eng.step"}),
    ("eng.sync", {"eng.step"}),
    ("eng.replay", {"eng.step"}),
    ("gw.flush", {"gw.pass"}),
    ("gw.yield", {"gw.pass"}),
    ("gw.write", {None}),
])
def test_on_nests_spans_by_parent_id(gateway_spans, child, parents):
    by_id = {s.sid: s for s in gateway_spans}
    mine = [s for s in gateway_spans if s.name == child]
    assert mine, f"no {child} span"
    for s in mine:
        p = by_id.get(s.parent)
        assert (p.name if p else None) in parents
        assert s.t0 <= s.t1
        if p is not None and child != "gw.hop":
            # a child lies inside its parent (the hop starts on the
            # parent's thread and ends on the executor's)
            assert p.t0 <= s.t0 and s.t1 <= p.t1
    if child == "eng.step":
        assert {s.pool for s in mine} == {"short"}
        assert {s.kind for s in mine} >= {"prefill", "decode"}
    if child in ("gw.handle", "router.route"):
        assert {s.rid for s in mine} == {0}


# ------------------------------------------ (c) the profiler's own clock
@pytest.fixture(scope="module")
def profiled(model, tmp_path_factory):
    tdir = str(tmp_path_factory.mktemp("trace"))
    eng = engine(model)
    submit(eng, 3)
    eng.step()                          # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tdir, profiler_options=opts)
    ann = jax.profiler.TraceAnnotation(TR.WINDOW)
    a = time.perf_counter_ns()
    ann.__enter__()
    anchor = (a + time.perf_counter_ns()) // 2
    eng.tracer.start()
    eng.run_to_completion(200)
    eng.tracer.stop()
    ann.__exit__(None, None, None)
    jax.profiler.stop_trace()
    planes = list(TR.load(tdir).planes)
    return planes, eng.tracer.take(), anchor


@pytest.mark.parametrize("name", ["eng.step", "eng.admit", "eng.alloc",
                                  "eng.dispatch", "eng.sync", "eng.replay"])
def test_spans_line_up_with_their_profiler_twins(profiled, name):
    planes, spans, anchor = profiled
    twins = sorted((a, b) for a, b, _ in TR.host_spans(planes, (name,)))
    mine = sorted((s.t0, s.t1) for s in spans if s.name == name)
    assert mine and len(twins) == len(mine)
    off = TR.host_spans(planes, (TR.WINDOW,))[0][0] - anchor
    for (a, b), (t0, t1) in zip(twins, mine):
        assert abs(a - (t0 + off)) < MS and abs(b - (t1 + off)) < MS


# --------------------------------------- (d) wall-clock queue wait
@pytest.mark.parametrize("held_by", ["slots", "blocks"])
def test_queue_wait_counts_a_request_held_behind_a_full_pool(model,
                                                             held_by):
    # "slots": one slot; "blocks": two slots but blocks for one request
    kw = {"n_max": 1} if held_by == "slots" else {"num_blocks": 3}
    eng = engine(model, **kw)
    t_before = time.perf_counter_ns()
    submit(eng, 2, prompt=20, new=20)
    t_after = time.perf_counter_ns()
    while not any(r is not None and r.rid == 1 for r in eng.slot_req):
        t_step = time.perf_counter_ns()
        eng.step()
    t_adm = time.perf_counter_ns()
    assert eng.queue_admissions == 2
    # request 1 waited at least until the step that admitted it began,
    # and neither request longer than until it was admitted
    assert t_step - t_after <= eng.queue_wait_ns <= 2 * (t_adm - t_before)
    assert (eng.admissions_deferred > 0) == (held_by == "blocks")
    eng.run_to_completion(500)
    assert eng.results[1].queue_iters > 0


# ------------------------------------------------- (e) KV block counters
@pytest.mark.parametrize("prefix_cache,decode_k", [(False, 1), (False, 4),
                                                   (True, 4)])
def test_kv_block_counters_follow_the_allocator(model, prefix_cache,
                                                decode_k):
    eng = engine(model, n_max=3, num_blocks=10, prefix_cache=prefix_cache,
                 decode_k=decode_k)
    rng = np.random.default_rng(1)
    shared = [int(t) for t in rng.integers(1, 900, 16)]
    for rid in range(6):
        eng.submit(ServeRequest(
            rid=rid, tokens=shared + [int(t) for t in rng.integers(
                1, 900, int(rng.integers(1, 20)))],
            max_new_tokens=int(rng.integers(2, 9))))
    steps = 0
    while eng.busy() and eng.iteration < 1000:
        r0, u0 = eng.kv_reserved_block_steps, eng.kv_used_block_steps
        d0 = eng.dispatches
        eng.step()
        eng.assert_block_invariants()
        referenced = {b for blocks in eng._slot_blocks for b in blocks}
        written = sum(-(-int(eng.slot_pos[s]) // eng.block_size)
                      for s in range(eng.n_max)
                      if eng.slot_req[s] is not None)
        if eng.dispatches == d0:
            assert eng.kv_reserved_block_steps == r0
            continue
        steps += 1
        assert eng.kv_reserved_block_steps - r0 == \
            len(referenced) + eng._reserved
        assert eng.kv_used_block_steps - u0 == written
        assert written <= sum(len(b) for b in eng._slot_blocks)
    assert len(eng.results) == 6 and steps > 0
    assert eng.kv_reserved_block_steps >= eng.kv_used_block_steps > 0


# ------------------------------------------------------ (f) /metrics
@pytest.mark.parametrize("family", [
    "fleetopt_queue_wait_seconds_sum", "fleetopt_queue_wait_seconds_count",
    "fleetopt_admissions_deferred_total",
    "fleetopt_kv_reserved_block_steps_total",
    "fleetopt_kv_used_block_steps_total"])
def test_new_metrics_families_render(model, family):
    cfg, params = model
    rt = FleetRuntime(cfg, params, boundaries=(64,), gammas=(1.4,),
                      n_maxes=(1, 1), c_maxes=(128, 256), c_chunk=16,
                      config=ServingConfig(paged=True))
    eng = rt.engines["short"]
    submit(eng, 2, prompt=10, new=3)
    eng.run_to_completion(200)
    text = render_prometheus(fleet_metrics(rt))
    assert f"# TYPE {family} counter" in text
    got = {ln.split("{")[1].split("}")[0]: float(ln.split()[-1])
           for ln in text.splitlines() if ln.startswith(family + "{")}
    assert set(got) == {'pool="short"', 'pool="long"'}
    assert got['pool="long"'] == 0
    expect = {"fleetopt_queue_wait_seconds_sum": eng.queue_wait_ns / 1e9,
              "fleetopt_queue_wait_seconds_count": 2,
              "fleetopt_admissions_deferred_total": 0,
              "fleetopt_kv_reserved_block_steps_total":
                  eng.kv_reserved_block_steps,
              "fleetopt_kv_used_block_steps_total":
                  eng.kv_used_block_steps}[family]
    assert got['pool="short"'] == pytest.approx(expect, rel=1e-5)


# ------------------------------------------------------- (g) readers
def _span(name, t0, t1, sid, parent=0, kind=""):
    return Span(name, t0 * MS, t1 * MS, sid, parent, "short", -1, kind)


SYNTHETIC = [
    _span("gw.pass", 0, 40, 1),
    _span("eng.step", 1, 11, 2, 1, "mixed"),      # 10 ms
    _span("eng.dispatch", 2, 4, 3, 2),            # 2
    _span("eng.sync", 5, 9, 4, 2),                # 4 -> host 4 ms
    _span("eng.step", 14, 20, 5, 1, "decode"),    # 6 ms
    _span("eng.sync", 15, 19, 6, 5),              # 4 -> host 2 ms
    _span("eng.step", 22, 23, 7, 1, "idle"),      # not a dispatch
    _span("eng.step", 30, 38, 8, 1, "decode"),    # 8, no children
]


def _counters(**short):
    base = {"dispatches": 1, "decode_dispatches": 1}
    return {"window": {"router": {"total": 0},
                       "short": dict(base, **short),
                       "long": dict(base, **{k: 0 for k in short})}}


@pytest.mark.parametrize("metric,record,expect", [
    ("sched.queue_wait_ms_mean", {"counters": _counters()}, None),
    ("sched.queue_wait_ms_mean", {"counters": _counters(
        queue_wait_ns=30 * MS, queue_admissions=4)}, 7.5),
    ("kv.reserved_over_used", {"counters": _counters()}, None),
    ("kv.reserved_over_used", {"counters": _counters(
        kv_reserved_block_steps=500, kv_used_block_steps=200)}, 2.5),
    ("step.host_ms_p50", {"program_spans": []}, None),
    ("step.host_ms_p50", {"program_spans": SYNTHETIC}, 4.0),
    ("gateway.between_steps_ms_p50", {"program_spans": []}, None),
    # gaps 11->14, 20->22, 23->30: 3, 2, 7 ms
    ("gateway.between_steps_ms_p50", {"program_spans": SYNTHETIC}, 3.0),
])
def test_readers(metric, record, expect):
    rec = H.Record(cfg={}, peak={})
    for k, v in record.items():
        setattr(rec, k, v)
    got = H.load_reader(metric)(rec)
    assert got == (None if expect is None else pytest.approx(expect))


# ------------------------------------ (h) idle gaps by program span
def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=int(start_ms * MS),
              duration_ns=int(dur_ms * MS))


@pytest.mark.parametrize("offset_ms", [0, 7_000_000])
def test_idle_gaps_are_named_by_the_innermost_program_span(offset_ms):
    # device busy 10..30 and 60..70 in a window 5..110 on the trace's
    # clock; the program's clock lags it by offset_ms
    device = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        ev("fusion.1", 10, 20), ev("fusion.2", 60, 10)])])
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        ev(TR.WINDOW, 5, 105)])])
    o = -offset_ms
    spans = [
        _span("gw.pass", 30 + o, 62 + o, 1),
        _span("eng.step", 28 + o, 40 + o, 2, 1),
        _span("eng.replay", 32 + o, 36 + o, 3, 2),
        _span("gw.write", 50 + o, 55 + o, 4),        # another task
        _span("host.gc", 80 + o, 90 + o, 5),
    ]
    res = PT.idle_by_program_span([device, host], spans, 5 * MS + o * MS)
    got = dict(res["by_span"])
    # idle: 5..10, 30..60, 70..110 = 75 ms
    assert res["idle_s"] == pytest.approx(0.075)
    assert got["eng.replay"] == pytest.approx(0.004)     # 32..36
    assert got["eng.step"] == pytest.approx(0.006)       # 30..32, 36..40
    # gw.pass alone over 40..50, 55..60; gw.write (latest started, same
    # depth) over 50..55
    assert got["gw.pass"] == pytest.approx(0.015)
    assert got["gw.write"] == pytest.approx(0.005)
    assert got["host.gc"] == pytest.approx(0.010)
    # none open: 5..10 and 70..80, 90..110
    assert got[PT.NO_PROGRAM_SPAN] == pytest.approx(0.035)
    assert res["unnamed_share"] == pytest.approx(0.035 / 0.075)
    assert PT.idle_by_program_span([host], spans, 0) is None


def test_longest_spans_names_where_a_stall_sits():
    spans = [_span("eng.step", 1000, 1100, 1, kind="decode"),
             _span("eng.step", 2000, 2990, 2, kind="mixed"),
             _span("host.gc", 3000, 3020, 3)]
    line = PT.longest_spans(spans, w0=0.5)
    assert line.split("; ") == [
        "eng.step 0.990000 s (short mixed) at 1.500 s",
        "host.gc 0.020000 s (short) at 2.500 s"]
