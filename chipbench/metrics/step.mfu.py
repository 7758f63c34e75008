"""Model step, whole step: percent of the chip's bf16 peak that the
engine steps of the window achieved. FLOPs are counted from shapes
(``flops.py``) for every prompt token prefilled and every token
decoded: projections, MLP, attention over the live causal context,
and the head for decoded tokens; over the summed wall time of all step
spans."""
from chipbench import flops as F


def read(rec):
    steps = [s for s in rec.spans if s["name"].startswith("step.")]
    secs = sum(s["t1"] - s["t0"] for s in steps)
    if not steps or secs <= 0:
        return None
    cfg = rec.cfg
    lin = F.linear_flops_per_token(cfg)
    head = F.head_flops_per_token(cfg)
    per_ctx = F.attention_flops(cfg, 1)
    total = 0.0
    for s in steps:
        for start, n in s["prefill"]:
            ctx = n * start + n * (n + 1) / 2
            total += n * lin + per_ctx * ctx
        for pos in s["decode"]:
            total += lin + head + per_ctx * (pos + 1)
    if total <= 0:
        return None
    return 100.0 * total / (secs * rec.peak["bf16_flops_per_s"])
