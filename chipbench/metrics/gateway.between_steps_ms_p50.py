"""Gateway: median milliseconds from the end of one engine step to the
start of the next in the gateway's drive loop (either pool): the lock,
the executor hop, the health check, the flush to the streams and the
yield to the HTTP handlers. From the program's own ``eng.step`` spans
over the window of a traced run (``rec.program_spans``); None where the
record holds none, as with a harness that does not yet keep them
(PERF.md, section 7)."""
import statistics


def read(rec):
    spans = getattr(rec, "program_spans", None) or []
    steps = sorted((s.t0, s.t1) for s in spans if s.name == "eng.step")
    gaps = [(b[0] - a[1]) / 1e6 for a, b in zip(steps, steps[1:])]
    return statistics.median(gaps) if gaps else None
