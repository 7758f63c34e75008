"""The roofline bound. The operation and byte counts are each model
module's, tested beside it (``test_dense_relu2.py``)."""
import pytest

from chipbench import flops as F


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert F.roofline_seconds(50.0, 1.0, peak) == pytest.approx(0.5)
    assert F.roofline_seconds(50.0, 20.0, peak) == pytest.approx(2.0)
