"""The least time a chip could take for counted work.

The counts themselves are the model module's (``models/<name>.py``:
``prefill_flops``, ``decode_flops``, ``decode_kernel_cost``), since only
it knows its layers: which attend a window, which hold experts. One
multiply-add counts as 2 FLOPs.
"""
from __future__ import annotations


def roofline_seconds(flops: float, byts: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               byts / peak["hbm_bytes_per_s"])
