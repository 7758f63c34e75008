"""The program's own spans (``repro.serving.tracing``) against a
profiler trace: the first chip's idle time split by the innermost
program span open at each instant, and the longest span of each name,
where a stall sits. It reads the trace through ``trace.py``'s helpers,
so the tests feed it a small synthetic trace too.

Nothing in the harness calls this yet: a traced run keeps no program
spans until the harness turns the program's tracer on (PERF.md, section
7, says which edits that takes).
"""
from __future__ import annotations

from typing import Dict, Optional

from chipbench.trace import WINDOW, device_planes, host_spans, op_events, union

NO_PROGRAM_SPAN = "no program span"


def idle_by_program_span(planes, spans, anchor_ns: int,
                         top_n: int = 12) -> Optional[dict]:
    """The first chip's idle time in the traced window, split by the
    innermost PROGRAM span open at each instant (the program's own
    recorder, ``repro.serving.tracing``; innermost = deepest in its
    parent chain, then latest started). ``spans`` carry ``name``,
    ``t0``, ``t1``, ``sid`` and ``parent`` on the recorder's clock;
    ``anchor_ns`` is that clock read as the WINDOW annotation opened,
    so the annotation's start in the trace minus it is the offset
    between the clocks. None without a window or a device operation."""
    planes = list(planes)
    win = host_spans(planes, (WINDOW,))
    devs = [p for p in device_planes(planes) if op_events(p)]
    if not win or not devs:
        return None
    lo, hi = min(w[0] for w in win), max(w[1] for w in win)
    off = lo - anchor_ns
    busy = union((max(a, lo), min(b, hi)) for a, b, _ in op_events(devs[0])
                 if b > lo and a < hi)
    gaps, prev = [], lo
    for a, b in busy + [(hi, hi)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    parent = {s.sid: s.parent for s in spans}
    depth: Dict[int, int] = {}

    def depth_of(sid: int) -> int:
        chain = []
        while sid and sid not in depth:
            chain.append(sid)
            sid = parent.get(sid, 0)
        d = depth.get(sid, 0)
        for c in reversed(chain):
            d += 1
            depth[c] = d
        return depth.get(chain[0], 0) if chain else d

    live = sorted((s.t0 + off, s.t1 + off, depth_of(s.sid), s.name)
                  for s in spans if s.t1 + off > lo and s.t0 + off < hi)
    by_name: Dict[str, int] = {}
    for a, b in gaps:
        over = [s for s in live if s[0] < b and s[1] > a]
        cuts = sorted({a, b} | {min(max(t, a), b) for s in over
                                 for t in s[:2]})
        for x, y in zip(cuts, cuts[1:]):
            open_ = [s for s in over if s[0] <= x and s[1] >= y]
            name = max(open_, key=lambda s: (s[2], s[0]))[3] if open_ \
                else NO_PROGRAM_SPAN
            by_name[name] = by_name.get(name, 0) + (y - x)
    idle = sum(b - a for a, b in gaps)
    return {
        "idle_s": idle / 1e9,
        "unnamed_share": by_name.get(NO_PROGRAM_SPAN, 0) / idle
        if idle else 0.0,
        "by_span": [[n, v / 1e9] for n, v in
                    sorted(by_name.items(), key=lambda kv: -kv[1])[:top_n]],
    }


def describe_idle(res: dict) -> str:
    """One line of ``idle_by_program_span``'s result."""
    return (f"{res['idle_s']:.6f} s idle, "
            f"{100 * res['unnamed_share']:.2f}% with no program span; "
            + "; ".join(f"{n} {v:.6f} s" for n, v in res["by_span"]))


def longest_spans(spans, w0: float, n: int = 10) -> str:
    """The longest program span of each name, longest first, each with
    its pool and kind and when it started (seconds into the window, which
    opened at ``w0`` on the perf_counter clock): where a stall sits."""
    top: Dict[str, object] = {}
    for s in spans:
        if s.name not in top or s.t1 - s.t0 > top[s.name].t1 - top[s.name].t0:
            top[s.name] = s
    out = []
    for s in sorted(top.values(), key=lambda s: s.t0 - s.t1)[:n]:
        what = " ".join(x for x in (s.pool, s.kind) if x)
        out.append(f"{s.name} {(s.t1 - s.t0) / 1e9:.6f} s"
                   + (f" ({what})" if what else "")
                   + f" at {s.t0 / 1e9 - w0:.3f} s")
    return "; ".join(out)
