"""Engine host: median milliseconds of a dispatching engine step spent
on the host outside the jitted call's upload and enqueue
(``eng.dispatch``) and the token sync (``eng.sync``): admission, block
allocation, input preparation and the replay of emitted tokens. From
the program's own ``eng.step`` spans over the window of a traced run
(``rec.program_spans``); None where the record holds none, as with a
harness that does not yet keep them (PERF.md, section 7)."""
import statistics


def read(rec):
    spans = getattr(rec, "program_spans", None) or []
    steps = {s.sid: s for s in spans
             if s.name == "eng.step" and s.kind not in ("", "idle")}
    if not steps:
        return None
    inner = dict.fromkeys(steps, 0)
    for s in spans:
        if s.name in ("eng.dispatch", "eng.sync") and s.parent in inner:
            inner[s.parent] += s.t1 - s.t0
    return statistics.median((st.t1 - st.t0 - inner[sid]) / 1e6
                             for sid, st in steps.items())
