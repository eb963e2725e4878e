"""The plain reference of the store: per-key last-writer-wins.

A version is ``(timestamp, record, sent)``: the timestamp is the pair
(Lamport clock, writer id) and the larger pair wins, clock first, then
writer id as a string.  ``sent`` is when the write was sent (host clock
of the window), which bounds what a read that ended earlier may return.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

Timestamp = Tuple[int, str]


class History:
    def __init__(self) -> None:
        self._versions: Dict[int, List[Tuple[Timestamp, np.ndarray, float]]] = {}

    def add(self, key: int, ts: Timestamp, record: np.ndarray,
            sent: float) -> None:
        self._versions.setdefault(key, []).append(
            ((int(ts[0]), str(ts[1])), np.asarray(record, np.float32), sent))

    def written_keys(self) -> List[int]:
        """Keys that hold a version besides the loaded one."""
        return [k for k, vs in self._versions.items() if len(vs) > 1]

    def winner(self, key: int) -> Tuple[Timestamp, np.ndarray]:
        ts, record, _sent = max(self._versions[key], key=lambda v: v[0])
        return ts, record

    def could_read(self, key: int, value: Any, ended: float) -> bool:
        """Whether ``value`` is, bit for bit, a version of ``key`` that
        was sent before the read ended (eventual consistency allows any
        of them, nothing else)."""
        if value is None:
            return False
        got = np.asarray(value)
        if got.dtype != np.float32 or got.size != self._size(key):
            return False
        raw = got.reshape(-1).view(np.uint32)
        return any(np.array_equal(raw, rec.reshape(-1).view(np.uint32))
                   for _ts, rec, sent in self._versions.get(key, ())
                   if sent <= ended)

    def _size(self, key: int) -> int:
        return self._versions[key][0][1].size


def same(expected: Tuple[Timestamp, np.ndarray], ts: Timestamp,
         record: Any) -> bool:
    """A stored (timestamp, record) equals the expected one bit for bit."""
    exp_ts, exp_rec = expected
    got = np.asarray(record, np.float32).reshape(-1)
    return ((int(ts[0]), str(ts[1])) == exp_ts
            and np.array_equal(got.view(np.uint32),
                               exp_rec.reshape(-1).view(np.uint32)))
