"""Engine scheduler: percent of the window's dispatches, both pools,
that carried a prefill chunk (a prefill alone or fused with decode
rows), from the engines' ``dispatches`` and ``decode_dispatches``
counters. The rest are decode scans of ``decode_k`` steps."""


def read(rec):
    pools = [v for k, v in rec.counters["window"].items() if k != "router"]
    total = sum(v["dispatches"] for v in pools)
    if total <= 0:
        return None
    prefill = sum(v["dispatches"] - v["decode_dispatches"] for v in pools)
    return 100.0 * prefill / total
