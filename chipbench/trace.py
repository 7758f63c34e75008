"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time (the union of the intervals in which an
operation ran on the chip), time per device operation, and the idle
gaps with the host span open during each.

It reads only duck-typed planes (``.name``, ``.lines``), lines
(``.name``, ``.events``) and events (``.name``, ``.start_ns``,
``.duration_ns``), as ``jax.profiler.ProfileData`` gives them, so the
tests feed it a small synthetic trace.

The measured window is the host span named ``WINDOW`` that the harness
opens when tracing starts and closes when it stops; everything is
clipped to it.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
HOST_SPANS = ("gateway.submit", "gateway.flush", "step.short", "step.long")
OPS_LINE = "XLA Ops"


def load(log_dir: str):
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log dir."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return ProfileData.from_file(files[-1])


def short_name(name: str) -> str:
    """An XLA op's name without its HLO text ("%fusion.3 = bf16[..]
    fusion(..)" -> "%fusion.3")."""
    return name.split(" = ", 1)[0]


def _events(line) -> List[Tuple[int, int, str]]:
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def device_planes(planes) -> list:
    return [p for p in planes if p.name.startswith("/device:")
            and "SparseCore" not in p.name]


def op_events(plane) -> List[Tuple[int, int, str]]:
    lines = list(plane.lines)
    ops = [ln for ln in lines if ln.name == OPS_LINE]
    if not ops:
        ops = [ln for ln in lines
               if ln.name not in ("Steps", "XLA Modules")]
    out = []
    for ln in ops:
        out.extend(_events(ln))
    return out


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def host_spans(planes, names: Sequence[str]) -> List[Tuple[int, int, str]]:
    out = []
    for p in planes:
        if p.name.startswith("/device:"):
            continue
        for ln in p.lines:
            out.extend(e for e in _events(ln) if e[2] in names)
    return out


def reduce(planes, kernel_patterns: Dict[str, str],
           top_n: int = 10) -> Optional[dict]:
    """The trace's summary, or None when it holds no window or no
    device operation in it."""
    planes = list(planes)
    win = host_spans(planes, (WINDOW,))
    if not win:
        return None
    lo, hi = min(w[0] for w in win), max(w[1] for w in win)
    devs = [p for p in device_planes(planes) if op_events(p)]
    if not devs:
        return None
    busy_ns, ops_ns = 0, {}
    kernels = {k: 0 for k in kernel_patterns}
    kernel_calls = {k: 0 for k in kernel_patterns}
    first_busy = None
    for p in devs:
        evs = [(max(a, lo), min(b, hi), n) for a, b, n in op_events(p)
               if b > lo and a < hi]
        u = union((a, b) for a, b, _ in evs)
        busy_ns += sum(b - a for a, b in u)
        if first_busy is None:
            first_busy = u
        for a, b, n in evs:
            ops_ns[short_name(n)] = ops_ns.get(short_name(n), 0) + (b - a)
            for k, pat in kernel_patterns.items():
                if pat.lower() in n.lower():
                    kernels[k] += b - a
                    kernel_calls[k] += 1
    nd = len(devs)
    window_ns = hi - lo
    # idle gaps of the first chip, named by the host span open in each
    gaps = []
    prev = lo
    for a, b in (first_busy or []) + [(hi, hi)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    spans = host_spans(planes, HOST_SPANS)
    by_name: Dict[str, int] = {}
    for a, b in gaps:
        mid = (a + b) // 2
        open_ = [s for s in spans if s[0] <= mid < s[1]]
        name = min(open_, key=lambda s: s[1] - s[0])[2] if open_ \
            else "no host span"
        by_name[name] = by_name.get(name, 0) + (b - a)
    top_ops = sorted(ops_ns.items(), key=lambda kv: -kv[1])[:top_n]
    return {
        "busy_s": busy_ns / nd / 1e9,
        "window_s": window_ns / 1e9,
        "devices": nd,
        "kernel_s": {k: v / nd / 1e9 for k, v in kernels.items()},
        "kernel_calls": kernel_calls,
        "device_ops": [[n, v / nd / 1e9] for n, v in top_ops],
        "idle_gaps": [[n, v / 1e9] for n, v in
                      sorted(by_name.items(), key=lambda kv: -kv[1])[:top_n]],
    }


def describe(planes, max_names: int = 12) -> List[str]:
    """A few lines on the trace's layout, for looking at it by hand."""
    out = []
    for p in planes:
        lines = list(p.lines)
        out.append(f"plane {p.name!r}: {len(lines)} lines")
        for ln in lines[:8]:
            evs = list(ln.events)
            names: Dict[str, int] = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + e.duration_ns
            top = sorted(names.items(), key=lambda kv: -kv[1])[:max_names]
            out.append(f"  line {ln.name!r}: {len(evs)} events; top: "
                       + "; ".join(f"{n[:60]}={v / 1e6:.2f}ms"
                                   for n, v in top))
    return out
