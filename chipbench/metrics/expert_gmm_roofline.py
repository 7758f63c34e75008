"""Kernels: percent of its roofline that the held experts' grouped
matmul (the ``expert_gmm`` kernel of a dropless expert layer) reached in
the traced seconds. The least time is, per call of an expert layer, the
larger of its bytes over HBM bandwidth and its FLOPs over the bf16
peak, as the model module counts them for the call's token rows
(``rec.model.expert_cost``), times the expert layers served; summed over
the traced steps and divided by the kernel's summed device time. A step
calls each expert layer once for its prefill chunks (their live tokens)
and once a decode pass: a fused step's decode rows, or each of a decode
scan's ``decode_k`` passes over the rows still live in it. None without
a trace, without the kernel in it, or for a model without experts."""
from chipbench import flops as F


def scan_passes(positions, k: int):
    """Rows per pass of a ``k``-pass decode scan, from the positions it
    decoded: a row decodes consecutive positions in its first passes."""
    runs, prev = [], None
    for p in positions:
        if prev is not None and p == prev + 1 and runs[-1] < k:
            runs[-1] += 1
        else:
            runs.append(1)
        prev = p
    return [n for n in (sum(r > j for r in runs) for j in range(k)) if n]


def read(rec):
    tr = rec.trace
    cost = getattr(rec.model, "expert_cost", None)
    if not tr or rec.trace_t0 is None or cost is None:
        return None
    ksec = tr["kernel_s"].get("expert_gmm", 0.0)
    if ksec <= 0:
        return None
    cfg = rec.cfg
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    k = cfg["serving"]["decode_k"]
    least = 0.0
    for s in rec.spans:
        if not s["name"].startswith("step.") or s["t0"] < rec.trace_t0 \
                or s["t1"] > rec.trace_t1:
            continue
        calls = [sum(n for _, n in s["prefill"])]
        if s["kind"] == "decode":
            calls += scan_passes(s["decode"], k)
        else:
            calls.append(len(s["decode"]))
        for rows in calls:
            if rows:
                least += layers * F.roofline_seconds(*cost(cfg, rows),
                                                     rec.peak)
    if least <= 0:
        return None
    return 100.0 * least / ksec
