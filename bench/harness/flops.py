"""Bytes the work needs, from shapes alone, and the device's peaks.

Every count is of useful work: the rows the traffic reads and writes,
never the padding a program adds to a batch.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict


def peaks_for(device_kind: str, bench_dir: Path) -> Dict[str, Any]:
    """The peaks of ``device_kind`` from ``bench/peaks.json``; a device
    that is not in the table is an error, never a default."""
    with open(bench_dir / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]


def slab_row_bytes(record_floats: int) -> int:
    """One stored row: float32 payload, int32 clock, int32 writer rank."""
    return 4 * record_floats + 4 + 4


def roofline_seconds(flops: float, nbytes: float, peaks: Dict[str, Any]
                     ) -> float:
    """The least time the chip needs: the larger of its two bounds."""
    return max(flops / float(peaks["bf16_flops_per_s"]),
               nbytes / float(peaks["hbm_bytes_per_s"]))
