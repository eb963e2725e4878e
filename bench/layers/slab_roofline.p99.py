"""Slab programs' share of their bytes roofline, %.

The floor is the bytes the traffic itself moves: each completed read
reads one stored row and each acknowledged update writes one row to each
replica, a row being the float32 record with its clock and writer
planes.  The time is the traced device time of every ``slab_*`` program
(``kernels/ops.py``), averaged over the chips."""

from bench.harness.flops import roofline_seconds


def read(window):
    seconds = window.device_seconds(["slab_"])
    if seconds is None or not window.peaks:
        return None
    return 100.0 * roofline_seconds(0.0, window.work["slab_bytes"],
                                    window.peaks) / seconds
