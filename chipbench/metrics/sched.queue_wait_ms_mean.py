"""Engine scheduler: mean wall-clock milliseconds a request waited in a
pool's queue, from ``engine.submit`` to admission into a slot, over the
admissions in the window, both pools (window deltas of the engines'
``queue_wait_ns`` and ``queue_admissions``). None where the window's
counters lack them, as with a harness that does not yet read them
(PERF.md, section 7), or nothing was admitted."""


def read(rec):
    pools = [v for k, v in rec.counters["window"].items() if k != "router"]
    if not pools or any("queue_wait_ns" not in v for v in pools):
        return None
    n = sum(v["queue_admissions"] for v in pools)
    if n <= 0:
        return None
    return sum(v["queue_wait_ns"] for v in pools) / n / 1e6
