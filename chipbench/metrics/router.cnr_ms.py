"""Router + C&R: mean milliseconds the gateway spent compressing a
borderline prompt, over the attempts in the window (``RouterStats``
deltas). None where no prompt was compressed."""


def read(rec):
    r = rec.counters["window"]["router"]
    if r["compression_attempts"] <= 0:
        return None
    return r["compression_ms_sum"] / r["compression_attempts"]
