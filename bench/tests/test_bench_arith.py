"""Byte counts against hand counts, and the peaks table."""

import pytest

from bench.harness import flops
from bench.harness.spec import BENCH_DIR


def test_slab_row_and_roofline_seconds():
    assert flops.slab_row_bytes(256) == 1024 + 4 + 4
    peaks = flops.peaks_for("TPU v5 lite", BENCH_DIR)
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    # bytes-bound: 819 MB at 819 GB/s is 1 ms, beating 1 GFLOP's 5 us
    assert flops.roofline_seconds(1e9, 819e6, peaks) == pytest.approx(1e-3)
    assert flops.roofline_seconds(197e12, 0, peaks) == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks_for("TPU v9 imaginary", BENCH_DIR)
    with pytest.raises(KeyError):
        flops.peaks_for("cpu", BENCH_DIR)
