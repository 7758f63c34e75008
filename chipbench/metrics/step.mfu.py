"""Model step, whole step: percent of the chip's bf16 peak that the
engine steps of the window achieved. FLOPs are the configuration's
model module's counts (``prefill_flops``, ``decode_flops`` of
``rec.model``) for every prompt chunk prefilled and every token decoded,
over the summed wall time of all step spans."""


def read(rec):
    steps = [s for s in rec.spans if s["name"].startswith("step.")]
    secs = sum(s["t1"] - s["t0"] for s in steps)
    if not steps or secs <= 0:
        return None
    cfg, model = rec.cfg, rec.model
    total = 0.0
    for s in steps:
        for start, n in s["prefill"]:
            total += model.prefill_flops(cfg, start, n)
        for pos in s["decode"]:
            total += model.decode_flops(cfg, pos)
    if total <= 0:
        return None
    return 100.0 * total / (secs * rec.peak["bf16_flops_per_s"])
