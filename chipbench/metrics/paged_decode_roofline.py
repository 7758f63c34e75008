"""Kernels: percent of its roofline that the paged decode kernel
reached in the traced seconds. The least time is, per live row and
decode step, the larger of its bytes over HBM bandwidth and its FLOPs
over the bf16 peak, as the configuration's model module counts them
for the row's live context (``rec.model.decode_kernel_cost``); summed
over the traced steps and divided by the kernel's summed device time
in the trace. None without a trace or without the kernel in it."""
from chipbench import flops as F


def read(rec):
    tr = rec.trace
    if not tr or rec.trace_t0 is None:
        return None
    ksec = tr["kernel_s"].get("paged_decode", 0.0)
    if ksec <= 0:
        return None
    least = 0.0
    for s in rec.spans:
        if not s["name"].startswith("step.") or s["t0"] < rec.trace_t0 \
                or s["t1"] > rec.trace_t1:
            continue
        for pos in s["decode"]:
            f, b = rec.model.decode_kernel_cost(rec.cfg, pos)
            least += F.roofline_seconds(f, b, rec.peak)
    if least <= 0:
        return None
    return 100.0 * least / ksec
