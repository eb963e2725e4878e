"""Batched tensor-lattice data plane: arena slabs, merge engine, planes.

Cloudburst's storage tier converges replicas purely by lattice merge
(paper §2.2, §5.2), and for tensor-valued payloads (parameter shards, KV
pages, metric vectors) that merge is the storage layer's compute hot-spot.
PR 1 batched the merge *compute*; this module also owns the replication
*wire format*, so arena-to-arena transfer (gossip, hinted handoff, cache
pushes, membership handoff) moves packed planes end-to-end and never
materializes per-key ``LWWLattice`` objects in steady state.

Architecture
============

``NodeRegistry``
    Order-preserving intern table: node-id *strings* -> int32 ranks.
    ``LWWLattice.merge`` breaks clock ties by comparing node ids as
    strings; the kernels compare int32 ranks.  Ranks are indices into the
    registry's *sorted* id list, so ``rank(a) >= rank(b)  <=>  a >= b``
    and the kernel tie-break is bit-identical to the Python one.  When a
    new id lands mid-stream the registry broadcasts a rank remap to every
    subscribed arena, which rewrites its stored node planes in one
    vectorized pass (rare: the node set is small and stable).

``LatticeArena``
    Columnar storage for tensor-valued LWW registers.  Keys are grouped
    into *slabs* by (payload shape, dtype); each slab holds contiguous
    ``(cap, D)`` value rows with parallel ``(cap, 1)`` int32 Lamport
    clock / node-rank planes — exactly the layout
    ``ops.lww_merge_many`` consumes, so a batched merge is one gather,
    one kernel launch and one scatter instead of K Python object merges.
    ``export_planes(keys)`` snapshots rows into a :class:`PlaneBatch`
    with vectorized gathers (no per-key objects).

``PlaneBatch`` / ``PlaneBuffer``  (the replication wire protocol)
    A ``PlaneBatch`` is the unit of arena-to-arena transfer: per slab
    group, a key list plus contiguous ``(K, D)`` value and ``(K, 1)``
    clock/node planes, where node entries index a batch-local
    ``node_ids`` intern table — the batch is self-describing, so it
    survives mid-stream registry rank remaps.  Non-arena lattices
    (opaque payloads, Set/Map/Causal, 64-bit exact-path payloads) ride
    alongside as an explicit per-key ``sidecar`` with unchanged
    semantics.  A ``PlaneBuffer`` is the mutable accumulator behind
    every replication channel (``StorageNode.inbox``, hinted handoffs,
    cache pushes): ``add`` packs eligible traffic row-by-row,
    ``add_batch`` splices whole batches, ``purge`` drops a deleted key,
    and ``split`` defers whole-key rows with the Table-2 staleness
    semantics of the per-item queues it replaces.

``MergeEngine``
    The façade every merge site routes through.  ``ingest_planes`` is
    the packed ingest: one ``ops.lww_merge_many`` launch per slab group
    merges incoming rows against stored rows (vectorized gather /
    scatter; duplicate keys in a batch are folded in delivery order via
    unique-key rounds).  ``merge_batch`` remains for object-carrying
    callers; opaque traffic keeps the exact per-key ``Lattice.merge``
    path via ``MergeEngine.fallback``.  ``MergeEngine.view`` is a
    MutableMapping over arena + fallback, which is what
    ``StorageNode.store`` / ``ExecutorCache.data`` expose.  Telemetry
    counters (``plane_keys``, ``plane_object_fallbacks``,
    ``arena.materializations``) let tests assert that steady-state
    replication constructs zero per-key lattice objects.
    ``watch_keys()`` returns a :class:`KeyWatch` that collects the net
    keys added to and removed from the engine (arena and fallback) until
    its owner drains it; an engine nobody watches records nothing.

Vector-clock helpers (``vc_classify_batch`` and friends) densify
``VectorClock`` pairs into ``(K, N)`` int32 matrices and classify
dominance through ``ops.vc_join_classify`` — the causal-cut checks in
``ExecutorCache._deps_covered`` ride these instead of per-entry dict
comparisons.

Shapes are padded to canonical buckets (K, D to powers of two, R to the
next power of two) so the jit cache stays small; padding replicates the
first candidate (LWW merge is idempotent) or zero rows whose winners are
discarded, so results are unaffected.  K buckets are additionally
rounded to a multiple of the merge mesh size so every launch is eligible
for K-sharding: with more than one local device, ``kernels.ops`` runs
``lww_merge_many`` / ``vc_join_classify`` under ``shard_map`` over a 1-D
device mesh (``launch.mesh.make_merge_mesh``), each device merging its
local rows — bit-identical to the single-device path, which is used
unchanged when the mesh has one device.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import math
import os
import time
import weakref

try:  # MutableMapping moved in 3.10
    from collections.abc import MutableMapping
except ImportError:  # pragma: no cover
    from collections import MutableMapping  # type: ignore

import numpy as np

from .lattices import Lattice, LWWLattice, VectorClock

_INT32_MAX = 2 ** 31


# ---------------------------------------------------------------------------
# Eligibility: which lattices ride the arena
# ---------------------------------------------------------------------------


# Dtypes jax silently downcasts with x64 disabled (the default): packing
# them through the kernels would truncate payload bits, so they keep the
# exact per-key Python path instead.
_JAX_DOWNCAST_DTYPES = frozenset(
    {"int64", "uint64", "float64", "complex128", "longdouble", "clongdouble"}
)


# ``dtype.name`` is rebuilt on every access, too slow for per-key paths:
# dtype -> (name, plane-eligible), memoized
_DTYPE_INFO: Dict[np.dtype, Tuple[str, bool]] = {}


def _dtype_info(dtype: np.dtype) -> Tuple[str, bool]:
    info = _DTYPE_INFO.get(dtype)
    if info is None:
        name = dtype.name
        info = (name, name not in _JAX_DOWNCAST_DTYPES and (
            dtype.kind in "biufc" or name.startswith(("bfloat16", "float8"))))
        _DTYPE_INFO[dtype] = info
    return info


def tensor_payload(value: Any) -> Optional[np.ndarray]:
    """Return the payload as an ndarray if it is dense tensor data the
    batched plane can carry losslessly."""
    arr: Optional[np.ndarray] = None
    if isinstance(value, np.ndarray):
        arr = value
    elif type(value).__module__.startswith("jax") and hasattr(value, "dtype"):
        try:
            arr = np.asarray(value)
        except Exception:
            return None
    if arr is None or not _dtype_info(arr.dtype)[1]:
        return None
    return arr


def is_arena_lww(lattice: Any) -> bool:
    """True iff this lattice can live in the arena: a tensor-valued LWW
    register whose Lamport pair fits the kernels' int32 planes."""
    if not isinstance(lattice, LWWLattice):
        return False
    clock, node = lattice.timestamp
    if not isinstance(clock, int) or not isinstance(node, str):
        return False
    if not 0 <= clock < _INT32_MAX:
        return False
    return tensor_payload(lattice.value) is not None


def oracle_lww_fold(lattices: Sequence[LWWLattice]) -> LWWLattice:
    """Pure-Python left fold of ``LWWLattice.merge`` — the equivalence
    oracle the batched plane must match bit-for-bit."""
    acc = lattices[0]
    for lat in lattices[1:]:
        acc = acc.merge(lat)
    return acc


def _bucket(n: int, minimum: int) -> int:
    """Round up to a power-of-two bucket (>= minimum) to bound jit shapes."""
    b = minimum
    while b < n:
        b *= 2
    return b


def _k_bucket(n: int, devices: Optional[int] = None) -> int:
    """K bucket: power of two, additionally a multiple of the merge mesh
    size so every padded launch is eligible for K-sharding.  The lcm
    keeps both properties for ANY device count (a power of two can never
    be doubled into divisibility by e.g. 3 or 6)."""
    b = _bucket(n, 8)
    if devices is None:
        from ..kernels import ops

        devices = ops.merge_mesh_size()
    if b % devices:
        b = math.lcm(b, devices)
    return b


def _contiguous_span(rows: np.ndarray) -> Optional[Tuple[int, int]]:
    """(start, stop) when ``rows`` is exactly start, start+1, ... — the
    zero-copy slice fast path for steady-state slab layouts (replicas
    that inserted keys in the same order)."""
    n = rows.shape[0]
    r0, r1 = int(rows[0]), int(rows[-1])
    if r1 - r0 != n - 1:
        return None
    if n > 1 and not bool((np.diff(rows) == 1).all()):
        return None
    return (r0, r1 + 1)


# ---------------------------------------------------------------------------
# Device-tier plumbing: mode knob, array dispatch, transfer telemetry
# ---------------------------------------------------------------------------

_DEVICE_TIER_ENV = "REPRO_DEVICE_TIER"
_DEVICE_TIER_CACHE: Optional[bool] = None


def device_tier_default() -> bool:
    """Whether new arenas keep their slabs device-resident by default:
    the ``REPRO_DEVICE_TIER`` env knob (1/true/on/yes).  Asking for the
    tier imports the device ops, so a broken install fails here rather
    than quietly serving from the host tier."""
    global _DEVICE_TIER_CACHE
    if _DEVICE_TIER_CACHE is None:
        flag = os.environ.get(_DEVICE_TIER_ENV, "").strip().lower()
        on = flag in ("1", "true", "on", "yes")
        if on:
            from ..kernels import ops  # noqa: F401
        _DEVICE_TIER_CACHE = on
    return _DEVICE_TIER_CACHE


def _is_device(arr: Any) -> bool:
    """True for jax device arrays (never numpy) — duck-typed so this
    module keeps importing without jax."""
    return (not isinstance(arr, np.ndarray)
            and type(arr).__module__.split(".")[0] in ("jaxlib", "jax"))


def _concat(parts: Sequence[Any]):
    """Concatenate plane chunks without forcing device chunks to host."""
    if len(parts) == 1:
        return parts[0]
    if any(_is_device(p) for p in parts):
        import jax.numpy as jnp

        return jnp.concatenate(list(parts))
    return np.concatenate(list(parts))


class KeyWatch:
    """Net membership delta of one :class:`MergeEngine` since the last
    :meth:`drain`: keys that became members (``added``) and keys that
    stopped being members (``removed``).  The two sets stay disjoint —
    a key's latest event wins — so applying a drained delta to the key
    set as of the previous drain gives the key set now."""

    __slots__ = ("added", "removed")

    def __init__(self) -> None:
        self.added: Set[str] = set()
        self.removed: Set[str] = set()

    def drain(self) -> Tuple[Set[str], Set[str]]:
        added, removed = self.added, self.removed
        self.added, self.removed = set(), set()
        return added, removed


def _note_added(watches: List[KeyWatch], keys: Sequence[str]) -> None:
    for w in watches:
        w.removed.difference_update(keys)
        w.added.update(keys)


def _note_removed(watches: List[KeyWatch], keys: Sequence[str]) -> None:
    for w in watches:
        w.added.difference_update(keys)
        w.removed.update(keys)


class _XferStats:
    """Host<->device boundary telemetry for one arena.

    Counts *value-plane* bytes crossing in each direction plus discrete
    device->host sync events; tiny row-index/scalar uploads are control
    plane and uncounted.  The zero-host-sync acceptance asserts ride
    these: steady-state device-tier gossip and warmed batched reads must
    leave the counters unchanged.  ``sync_s`` is the host wall time spent
    blocked in those device->host copies.
    """

    __slots__ = ("h2d_bytes", "d2h_bytes", "device_syncs", "sync_s")

    def __init__(self) -> None:
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.device_syncs = 0
        self.sync_s = 0.0


# ---------------------------------------------------------------------------
# Node registry: strings -> order-preserving int32 ranks
# ---------------------------------------------------------------------------


class NodeRegistry:
    """Interns node-id strings as ranks in sorted order.

    The sorted invariant is what makes the kernels' int tie-break agree
    with Python's string tie-break.  Inserting a new id shifts the ranks
    of ids that sort after it; subscribers (arenas) receive the old->new
    rank remap and rewrite their stored node planes.
    """

    __slots__ = ("_ids", "_rank", "_subscribers")

    def __init__(self) -> None:
        self._ids: List[str] = []
        self._rank: Dict[str, int] = {}
        # weakrefs: a registry outlives nodes/caches (it is tier-wide), so
        # strong refs would pin every removed node's arena forever
        self._subscribers: List["weakref.ref[LatticeArena]"] = []

    def subscribe(self, arena: "LatticeArena") -> None:
        self._subscribers.append(weakref.ref(arena))

    def rank(self, node_id: str) -> int:
        return self._rank[node_id]

    def node_id(self, rank: int) -> str:
        return self._ids[rank]

    def __len__(self) -> int:
        return len(self._ids)

    def ensure(self, node_ids: Sequence[str]) -> None:
        """Intern any unseen ids; remap subscribers if ranks shifted."""
        fresh = {nid for nid in node_ids if nid not in self._rank}
        if not fresh:
            return
        old = self._ids
        merged = sorted(set(old) | fresh)
        new_rank = {nid: i for i, nid in enumerate(merged)}
        remap = (
            np.asarray([new_rank[nid] for nid in old], np.int32)
            if old else None
        )
        self._ids = merged
        self._rank = new_rank
        if remap is not None:
            alive = []
            for ref in self._subscribers:
                arena = ref()
                if arena is not None:
                    arena._remap_ranks(remap)
                    alive.append(ref)
            self._subscribers = alive


# ---------------------------------------------------------------------------
# Arena slabs: contiguous (K, D) payloads + (K, 1) Lamport planes
# ---------------------------------------------------------------------------

_GroupKey = Tuple[Tuple[int, ...], str]  # (payload shape, dtype name)


# ---------------------------------------------------------------------------
# The replication wire format: packed planes + per-key sidecar
# ---------------------------------------------------------------------------


class PlaneGroup:
    """Packed rows of one (payload shape, dtype) slab group.

    ``node_idx`` entries index the owning batch's ``node_ids`` table (NOT
    a registry's ranks): the group is self-describing on the wire.
    """

    __slots__ = ("shape", "dtype", "keys", "vals", "clocks", "node_idx")

    def __init__(self, shape: Tuple[int, ...], dtype: np.dtype,
                 keys: List[str], vals: np.ndarray, clocks: np.ndarray,
                 node_idx: np.ndarray):
        self.shape = shape
        self.dtype = dtype
        self.keys = keys              # length K; duplicates allowed
        self.vals = vals              # (K, D) payload rows
        self.clocks = clocks          # (K, 1) int32 Lamport clocks
        self.node_idx = node_idx      # (K, 1) int32 -> batch.node_ids

    def __len__(self) -> int:
        return len(self.keys)

    def take(self, idx: Sequence[int]) -> "PlaneGroup":
        sel = np.asarray(idx, np.int64)
        return PlaneGroup(self.shape, self.dtype,
                          [self.keys[i] for i in idx],
                          self.vals[sel], self.clocks[sel],
                          self.node_idx[sel])

    def is_device(self) -> bool:
        return _is_device(self.vals)

    def to_host(self) -> "PlaneGroup":
        """Copy device planes to host numpy (the cross-node wire edge);
        host groups pass through untouched."""
        if not self.is_device():
            return self
        import jax

        vals, clocks, node_idx = jax.device_get(
            (self.vals, self.clocks, self.node_idx))
        return PlaneGroup(self.shape, self.dtype, list(self.keys),
                          vals, clocks, node_idx)


class PlaneBatch:
    """The unit of arena-to-arena replication: packed plane groups plus a
    per-key sidecar for lattices the planes cannot carry.

    Never holds per-key lattice objects for packed traffic — that is the
    whole point.  ``iter_entries`` materializes objects and exists for
    tests/debugging only.
    """

    __slots__ = ("node_ids", "groups", "sidecar")

    def __init__(self, node_ids: Optional[List[str]] = None):
        self.node_ids: List[str] = list(node_ids or [])
        self.groups: Dict[_GroupKey, PlaneGroup] = {}
        self.sidecar: List[Tuple[str, Lattice]] = []

    def packed_len(self) -> int:
        return sum(len(g) for g in self.groups.values())

    def __len__(self) -> int:
        return self.packed_len() + len(self.sidecar)

    def __bool__(self) -> bool:
        return len(self) > 0

    def keys(self) -> List[str]:
        out: List[str] = []
        for g in self.groups.values():
            out.extend(g.keys)
        out.extend(k for k, _ in self.sidecar)
        return out

    def byte_size(self) -> int:
        """Approximate wire size — drives the batched latency models
        (one clock advance per batch, sized by total payload bytes)."""
        n = sum(
            g.vals.nbytes + g.clocks.nbytes + g.node_idx.nbytes
            for g in self.groups.values()
        )
        return n + sum(v.byte_size() for _, v in self.sidecar)

    def to_host(self, xfer: Optional[_XferStats] = None) -> "PlaneBatch":
        """Copy any device-resident groups to host numpy — the explicit
        cross-node wire edge.  Counts one sync (plus the plane bytes)
        per device group against ``xfer`` when given."""
        out = PlaneBatch(self.node_ids)
        for group, pg in self.groups.items():
            t0 = time.perf_counter()
            host = pg.to_host()
            if xfer is not None and host is not pg:
                xfer.sync_s += time.perf_counter() - t0
                xfer.device_syncs += 1
                xfer.d2h_bytes += (host.vals.nbytes + host.clocks.nbytes
                                   + host.node_idx.nbytes)
            out.groups[group] = host
        out.sidecar = list(self.sidecar)
        return out

    def block_until_ready(self) -> "PlaneBatch":
        """Wait for any device-resident planes (benchmark timing edge)."""
        for pg in self.groups.values():
            if pg.is_device():
                pg.vals.block_until_ready()
        return self

    def iter_entries(self):
        """Materialize (key, Lattice) pairs — for object-consuming
        callers only (tests, the causal dep path); packed consumers
        ingest the planes directly.  Device groups convert once (one
        bulk transfer), not per row."""
        for g in self.groups.values():
            g = g.to_host()
            for i, key in enumerate(g.keys):
                ts = (int(g.clocks[i, 0]),
                      self.node_ids[int(g.node_idx[i, 0])])
                yield key, LWWLattice(ts, g.vals[i].copy().reshape(g.shape))
        yield from self.sidecar


class _GroupAccum:
    """Growable row accumulator behind one PlaneBuffer group.

    Two append paths: per-item ``add_row`` collects row views (stacked
    once at drain), and ``add_chunk`` splices whole packed chunks in O(1)
    — a batch forwarded through a buffer costs a list append, and a
    single-chunk drain hands the arrays through without copying.
    """

    __slots__ = ("shape", "dtype", "keys", "flats", "clocks", "nodes",
                 "chunks")

    _Chunk = Tuple[List[str], np.ndarray, np.ndarray, np.ndarray]

    def __init__(self, shape: Tuple[int, ...], dtype: np.dtype):
        self.shape = shape
        self.dtype = dtype
        self.keys: List[str] = []
        self.flats: List[np.ndarray] = []   # 1-D row views, stacked on drain
        self.clocks: List[int] = []
        self.nodes: List[int] = []          # buffer-local node indices
        self.chunks: List["_GroupAccum._Chunk"] = []

    def __len__(self) -> int:
        return len(self.keys) + sum(len(c[0]) for c in self.chunks)

    def add_row(self, key: str, flat: np.ndarray, clock: int,
                node: int) -> None:
        self.keys.append(key)
        self.flats.append(flat)
        self.clocks.append(clock)
        self.nodes.append(node)

    def add_chunk(self, keys: List[str], vals: np.ndarray,
                  clocks: np.ndarray, nodes: np.ndarray) -> None:
        self.chunks.append((keys, vals, clocks, nodes))

    def has_key(self, key: str) -> bool:
        return (key in self.keys
                or any(key in c[0] for c in self.chunks))

    def _normalize(self) -> "_GroupAccum._Chunk":
        """Fold rows + chunks into a single chunk (rare paths only)."""
        if self.keys:
            self.add_chunk(
                list(self.keys), np.stack(self.flats),
                np.asarray(self.clocks, np.int32).reshape(-1, 1),
                np.asarray(self.nodes, np.int32).reshape(-1, 1))
            self.keys, self.flats = [], []
            self.clocks, self.nodes = [], []
        if len(self.chunks) != 1:
            keys = [k for c in self.chunks for k in c[0]]
            self.chunks = [(
                keys,
                _concat([c[1] for c in self.chunks]),
                _concat([c[2] for c in self.chunks]),
                _concat([c[3] for c in self.chunks]),
            )]
        return self.chunks[0]

    def select(self, keep: Sequence[int]) -> None:
        keys, vals, clocks, nodes = self._normalize()
        sel = np.asarray(keep, np.int64)
        self.chunks = [([keys[i] for i in keep], vals[sel], clocks[sel],
                        nodes[sel])]

    def to_group(self) -> PlaneGroup:
        keys, vals, clocks, nodes = self._normalize()
        return PlaneGroup(self.shape, self.dtype, keys, vals, clocks, nodes)


class PlaneBuffer:
    """Mutable accumulator behind a replication channel (gossip inbox,
    hinted handoff, cache push queue).

    Arena-eligible traffic is packed on ``add`` (the payload row is held
    as a flat view; stacking happens once at drain); everything else
    lands in the sidecar.  ``split`` pops deliverable items as a
    :class:`PlaneBatch`, deferring whole-key rows with probability
    ``defer_prob`` — row-granular, matching the per-item deferral of the
    ``List[(key, lattice)]`` queues this replaces.
    """

    __slots__ = ("_node_ids", "_node_pos", "_groups", "_sidecar")

    def __init__(self) -> None:
        self._node_ids: List[str] = []
        self._node_pos: Dict[str, int] = {}
        self._groups: Dict[_GroupKey, _GroupAccum] = {}
        self._sidecar: List[Tuple[str, Lattice]] = []

    def _intern(self, node_id: str) -> int:
        pos = self._node_pos.get(node_id)
        if pos is None:
            pos = len(self._node_ids)
            self._node_ids.append(node_id)
            self._node_pos[node_id] = pos
        return pos

    def _accum(self, group: _GroupKey, shape: Tuple[int, ...],
               dtype: np.dtype) -> _GroupAccum:
        acc = self._groups.get(group)
        if acc is None:
            acc = _GroupAccum(shape, dtype)
            self._groups[group] = acc
        return acc

    def __len__(self) -> int:
        return (sum(len(a) for a in self._groups.values())
                + len(self._sidecar))

    def __bool__(self) -> bool:
        return len(self) > 0

    def add(self, key: str, value: Lattice) -> None:
        """Queue one update: packed when arena-eligible, sidecar else."""
        if is_arena_lww(value):
            arr = tensor_payload(value.value)
            clock, node_id = value.timestamp
            acc = self._accum((tuple(arr.shape), _dtype_info(arr.dtype)[0]),
                              tuple(arr.shape), arr.dtype)
            acc.add_row(key, arr.reshape(-1), clock, self._intern(node_id))
        else:
            self._sidecar.append((key, value))

    def add_batch(self, batch: PlaneBatch) -> None:
        """Splice a packed batch in: O(1) per group (the node-index remap
        through the buffer's intern table is the only per-row work)."""
        remap = np.asarray([self._intern(n) for n in batch.node_ids]
                           or [0], np.int32)
        for group, pg in batch.groups.items():
            if not len(pg):
                continue
            acc = self._accum(group, pg.shape, pg.dtype)
            if _is_device(pg.node_idx):  # remap on device: no implicit sync
                import jax.numpy as jnp

                nodes = jnp.take(
                    jnp.asarray(remap), pg.node_idx[:, 0]).reshape(-1, 1)
            else:
                nodes = remap[pg.node_idx[:, 0]].reshape(-1, 1)
            acc.add_chunk(list(pg.keys), pg.vals, pg.clocks, nodes)
        self._sidecar.extend(batch.sidecar)

    def purge(self, key: str) -> None:
        """Drop every queued row/sidecar entry for ``key`` (delete path)."""
        for group, acc in list(self._groups.items()):
            if acc.has_key(key):
                keys = acc._normalize()[0]
                keep = [i for i, k in enumerate(keys) if k != key]
                if keep:
                    acc.select(keep)
                else:
                    del self._groups[group]
        self._sidecar = [(k, v) for k, v in self._sidecar if k != key]

    def drain(self) -> PlaneBatch:
        """Pop everything as one PlaneBatch."""
        return self.split(None, 0.0)

    def split(self, rng, defer_prob: float) -> PlaneBatch:
        """Pop deliverable items; each row/sidecar entry independently
        defers (stays queued) with probability ``defer_prob``."""
        batch = PlaneBatch(self._node_ids)
        if rng is None or defer_prob <= 0.0:
            for group, acc in self._groups.items():
                batch.groups[group] = acc.to_group()
            batch.sidecar = self._sidecar
            self._groups = {}
            self._sidecar = []
            return batch
        for group, acc in list(self._groups.items()):
            n = len(acc)
            defer = [i for i in range(n) if rng.random() < defer_prob]
            if not defer:
                batch.groups[group] = acc.to_group()
                del self._groups[group]
                continue
            kept = set(defer)
            deliver = [i for i in range(n) if i not in kept]
            if deliver:
                batch.groups[group] = acc.to_group().take(deliver)
            acc.select(defer)
        deliver_sc, keep_sc = [], []
        for item in self._sidecar:
            (keep_sc if rng.random() < defer_prob else deliver_sc).append(item)
        batch.sidecar = deliver_sc
        self._sidecar = keep_sc
        return batch


class _Slab:
    __slots__ = ("shape", "dtype", "dim", "vals", "clocks", "nodes", "rows",
                 "row_keys")

    _INITIAL_CAP = 8

    def __init__(self, shape: Tuple[int, ...], dtype: np.dtype):
        self.shape = shape
        self.dtype = dtype
        self.dim = int(np.prod(shape)) if shape else 1
        cap = self._INITIAL_CAP
        self.vals = np.zeros((cap, self.dim), dtype)
        self.clocks = np.zeros((cap, 1), np.int32)
        self.nodes = np.zeros((cap, 1), np.int32)
        self.rows: Dict[str, int] = {}
        self.row_keys: List[str] = []  # row index -> key (O(1) drop)

    def _alloc(self, key: str) -> int:
        row = self.rows.get(key)
        if row is not None:
            return row
        row = len(self.rows)
        if row >= self.vals.shape[0]:
            new_cap = self.vals.shape[0] * 2
            for name in ("vals", "clocks", "nodes"):
                old = getattr(self, name)
                grown = np.zeros((new_cap,) + old.shape[1:], old.dtype)
                grown[: old.shape[0]] = old
                setattr(self, name, grown)
        self.rows[key] = row
        self.row_keys.append(key)
        return row

    def set_row(self, key: str, clock: int, rank: int, flat: np.ndarray) -> None:
        row = self._alloc(key)
        self.vals[row] = flat
        self.clocks[row, 0] = clock
        self.nodes[row, 0] = rank

    def drop(self, key: str) -> None:
        """Remove a key, keeping rows dense (swap the last row in)."""
        row = self.rows.pop(key)
        last = len(self.rows)
        if row != last:
            last_key = self.row_keys[last]
            self.vals[row] = self.vals[last]
            self.clocks[row] = self.clocks[last]
            self.nodes[row] = self.nodes[last]
            self.rows[last_key] = row
            self.row_keys[row] = last_key
        self.row_keys.pop()


class _DeviceSlab:
    """Device-resident twin of :class:`_Slab`.

    The (cap, D) value plane and (cap, 1) clock/node planes are jax
    arrays — row-sharded over the "kvs" mesh when the capacity divides
    (``ops.slab_place``) — and every update goes through the donated
    fused jits in ``kernels.ops``, so the buffers mutate in place and
    steady-state merge traffic never stages on the host.  Key -> row
    bookkeeping (dicts) stays host-side: row *indices* are control
    plane; only payloads live on the device.

    The top row (``cap - 1``) is a scratch row, never key-mapped:
    padded scatter lanes target it with identical bytes, which keeps
    duplicate-index scatters deterministic (XLA leaves the winning
    duplicate unspecified).  Capacities start at the K bucket and double
    (growth re-places the planes), so the scratch row moves but every
    key-mapped row is fully written before it is ever read.
    """

    __slots__ = ("shape", "dtype", "dim", "vals", "clocks", "nodes", "rows",
                 "row_keys", "xfer")

    def __init__(self, shape: Tuple[int, ...], dtype: np.dtype,
                 xfer: _XferStats):
        from ..kernels import ops

        self.shape = shape
        self.dtype = dtype
        self.dim = int(np.prod(shape)) if shape else 1
        cap = _k_bucket(_Slab._INITIAL_CAP)
        self.vals = ops.slab_zeros(cap, self.dim, dtype)
        self.clocks = ops.slab_zeros(cap, 1, np.int32)
        self.nodes = ops.slab_zeros(cap, 1, np.int32)
        self.rows: Dict[str, int] = {}
        self.row_keys: List[str] = []
        self.xfer = xfer

    @property
    def cap(self) -> int:
        return self.vals.shape[0]

    @property
    def scratch(self) -> int:
        return self.cap - 1

    def _alloc(self, key: str) -> int:
        row = self.rows.get(key)
        if row is not None:
            return row
        row = len(self.rows)
        if row >= self.cap - 1:  # keep the top row free as scratch
            from ..kernels import ops

            self.vals, self.clocks, self.nodes = ops.slab_grow(
                self.vals, self.clocks, self.nodes, self.cap * 2)
        self.rows[key] = row
        self.row_keys.append(key)
        return row

    def set_row(self, key: str, clock: int, rank: int,
                flat: np.ndarray) -> None:
        from ..kernels import ops

        row = self._alloc(key)
        if not _is_device(flat):
            self.xfer.h2d_bytes += flat.nbytes
        self.vals, self.clocks, self.nodes = ops.slab_set_row(
            self.vals, self.clocks, self.nodes, row, clock, rank, flat)

    def drop(self, key: str) -> None:
        """Remove a key, keeping rows dense (swap the last row in).

        The vacated last row keeps its stale bytes on device — it is
        unmapped, and any re-allocation fully overwrites it before any
        read, so a deleted key can never resurrect from the live
        donated buffers.
        """
        from ..kernels import ops

        row = self.rows.pop(key)
        last = len(self.rows)
        if row != last:
            last_key = self.row_keys[last]
            self.vals, self.clocks, self.nodes = ops.slab_move_row(
                self.vals, self.clocks, self.nodes, last, row)
            self.rows[last_key] = row
            self.row_keys[row] = last_key
        self.row_keys.pop()

    # -- batched write-backs (the merge-engine entry points) ---------------
    def _pad_np(self, rows: np.ndarray, clocks, ranks, vals):
        """Pad host-side inputs to the K bucket: pad lanes scatter zeros
        into the scratch row (identical bytes -> deterministic), and the
        bucketed shapes keep the jit cache small."""
        kk = len(rows)
        Kp = _k_bucket(kk)
        rows_in = np.full(Kp, self.scratch, np.int32)
        rows_in[:kk] = rows
        in_c = np.zeros((Kp, 1), np.int32)
        in_c[:kk] = clocks
        in_n = np.zeros((Kp, 1), np.int32)
        in_n[:kk] = ranks
        in_v = np.zeros((Kp, self.dim), self.dtype)
        in_v[:kk] = vals
        self.xfer.h2d_bytes += in_v.nbytes + in_c.nbytes + in_n.nbytes
        return rows_in, in_c, in_n, in_v

    def write_rows(self, rows: np.ndarray, clocks, ranks, vals) -> None:
        """Multi-row overwrite scatter (bulk_write / scatter_existing)."""
        from ..kernels import ops

        if _is_device(vals):
            rows_in, in_c, in_n, in_v = (
                np.asarray(rows, np.int32), clocks, ranks, vals)
        else:
            rows_in, in_c, in_n, in_v = self._pad_np(rows, clocks, ranks, vals)
        self.vals, self.clocks, self.nodes = ops.slab_write_rows(
            self.vals, self.clocks, self.nodes, rows_in, in_c, in_n, in_v)

    def ingest_rows(self, rows: np.ndarray, has: np.ndarray,
                    clocks, ranks, vals) -> None:
        """Fused pairwise ingest: every lane's target row exists (callers
        allocate first); ``has`` marks lanes with a stored value."""
        from ..kernels import ops

        if _is_device(vals):
            rows_in = np.asarray(rows, np.int32)
            has_in = np.asarray(has, bool).reshape(-1, 1)
            in_c, in_n, in_v = clocks, ranks, vals
        else:
            kk = len(rows)
            rows_in, in_c, in_n, in_v = self._pad_np(rows, clocks, ranks, vals)
            has_in = np.zeros((len(rows_in), 1), bool)
            has_in[:kk, 0] = has
        self.vals, self.clocks, self.nodes = ops.slab_ingest_rows(
            self.vals, self.clocks, self.nodes, rows_in, has_in,
            in_c, in_n, in_v)

    def ingest_multi(self, urows: np.ndarray, idx: np.ndarray,
                     stored_take: Sequence[int], clocks, ranks,
                     vals) -> None:
        """Fused R-candidate ingest for duplicate-key batches: ``idx``
        (R, U) indexes [incoming; gathered stored] per unique key."""
        from ..kernels import ops

        R, U = idx.shape
        Rp, Up = _bucket(R, 2), _k_bucket(U)
        kk, S = len(clocks), len(stored_take)
        if not _is_device(vals):
            # host rows pad to the K bucket like ``_pad_np`` (pad lanes
            # are never indexed), which moves the stored candidates up
            kin = _k_bucket(kk)
            pad = [(0, kin - kk), (0, 0)]
            clocks = np.pad(np.asarray(clocks, np.int32), pad)
            ranks = np.pad(np.asarray(ranks, np.int32), pad)
            vals = np.pad(np.asarray(vals), pad)
            idx = np.where(idx >= kk, idx + (kin - kk), idx)
            self.xfer.h2d_bytes += vals.nbytes + clocks.nbytes + ranks.nbytes
        take = np.full(_bucket(S, 8), self.scratch, np.int32)
        take[:S] = stored_take
        urows_in = np.full(Up, self.scratch, np.int32)
        urows_in[:U] = urows
        idx_in = np.empty((Rp, Up), np.int32)
        idx_in[:R, :U] = idx
        idx_in[R:, :U] = idx[0]       # repeat a candidate: idempotent
        idx_in[:, U:] = idx[0, 0]     # pad columns all write one winner
        self.vals, self.clocks, self.nodes = ops.slab_ingest_multi(
            self.vals, self.clocks, self.nodes, urows_in, idx_in, take,
            clocks, ranks, vals)


class LatticeArena:
    """Columnar tensor-LWW storage grouped into shape/dtype slabs."""

    def __init__(self, registry: NodeRegistry,
                 device: Optional[bool] = None):
        self.registry = registry
        # device mode: slabs live as donated jax arrays; host numpy slabs
        # otherwise (the default, and the fallback sans jax)
        self.device = device_tier_default() if device is None else bool(device)
        self._xfer = _XferStats()
        self._slabs: Dict[_GroupKey, _Slab] = {}
        self._key_group: Dict[str, _GroupKey] = {}
        # the owning engine's membership watches (MergeEngine.watch_keys);
        # every path below that adds or drops a key feeds them, once per
        # call where the path is batched — and only when the list is
        # non-empty, so an unwatched arena does no extra per-key work
        self._watches: List[KeyWatch] = []
        # bumps whenever the key -> (slab, row) layout changes (new key,
        # delete, cross-group move) — read-plan caches key off it; pure
        # row-content updates (gossip, puts over existing keys) do NOT
        # bump, so steady-state traffic never invalidates a plan
        self.layout_version = 0
        # memoized LWWLattice per key so repeated reads cost a dict hit,
        # not an O(D) payload copy; invalidated on any row write
        self._materialized: Dict[str, LWWLattice] = {}
        # telemetry: per-key LWWLattice constructions (memo misses).  The
        # plane wire format exists so replication paths keep this at zero.
        self.materializations = 0
        registry.subscribe(self)

    # -- transfer telemetry (device tier) ---------------------------------
    @property
    def h2d_bytes(self) -> int:
        return self._xfer.h2d_bytes

    @property
    def d2h_bytes(self) -> int:
        return self._xfer.d2h_bytes

    @property
    def device_syncs(self) -> int:
        return self._xfer.device_syncs

    @property
    def device_sync_s(self) -> float:
        return self._xfer.sync_s

    def reset_transfer_stats(self) -> None:
        """Zero the transfer counters in place — the slabs alias this
        ``_XferStats`` object, so benches/tests can window device-tier
        measurements without rebuilding the arena."""
        self._xfer.h2d_bytes = 0
        self._xfer.d2h_bytes = 0
        self._xfer.device_syncs = 0
        self._xfer.sync_s = 0.0

    # -- plumbing -------------------------------------------------------------
    @staticmethod
    def group_of(arr: np.ndarray) -> _GroupKey:
        return (tuple(arr.shape), _dtype_info(arr.dtype)[0])

    def _remap_ranks(self, remap: np.ndarray) -> None:
        for slab in self._slabs.values():
            if isinstance(slab, _DeviceSlab):
                from ..kernels import ops

                slab.nodes = ops.slab_remap_nodes(slab.nodes, remap)
            else:
                slab.nodes = remap[slab.nodes].astype(np.int32)
        self._materialized.clear()  # conservative: rank planes just moved

    def slab_for(self, group: _GroupKey, arr: np.ndarray) -> _Slab:
        return self.slab_for_meta(group, tuple(arr.shape), arr.dtype)

    def slab_for_meta(self, group: _GroupKey, shape: Tuple[int, ...],
                      dtype: np.dtype) -> _Slab:
        slab = self._slabs.get(group)
        if slab is None:
            slab = (_DeviceSlab(shape, dtype, self._xfer) if self.device
                    else _Slab(shape, dtype))
            self._slabs[group] = slab
        return slab

    def group_key_of(self, key: str) -> Optional[_GroupKey]:
        return self._key_group.get(key)

    # -- mapping-style access -------------------------------------------------
    def __contains__(self, key: str) -> bool:
        return key in self._key_group

    def __len__(self) -> int:
        return len(self._key_group)

    def keys(self):
        return self._key_group.keys()

    def set(self, key: str, lattice: LWWLattice) -> None:
        """Raw overwrite (no merge) — routing/packing only."""
        arr = tensor_payload(lattice.value)
        assert arr is not None, "arena.set requires a tensor payload"
        group = self.group_of(arr)
        prev = self._key_group.get(key)
        if prev is not None and prev != group:
            self._slabs[prev].drop(key)
        if prev != group:
            self.layout_version += 1
            if prev is None and self._watches:
                _note_added(self._watches, (key,))
        clock, node_id = lattice.timestamp
        self.registry.ensure((node_id,))
        slab = self.slab_for(group, arr)
        slab.set_row(key, clock, self.registry.rank(node_id), arr.reshape(-1))
        self._key_group[key] = group
        self._materialized.pop(key, None)

    def set_raw(self, key: str, group: _GroupKey, clock: int, rank: int,
                flat: np.ndarray) -> None:
        """Raw row overwrite for a batched caller, which notes the keys
        it adds to the watches itself (once per batch)."""
        prev = self._key_group.get(key)
        if prev is not None and prev != group:
            self._slabs[prev].drop(key)
        if prev != group:
            self.layout_version += 1
        self._slabs[group].set_row(key, clock, rank, flat)
        self._key_group[key] = group
        self._materialized.pop(key, None)

    def get(self, key: str) -> Optional[LWWLattice]:
        """Materialize the register (payload copied: lattices are frozen
        values, and the backing row mutates on future merges).  Repeat
        reads hit the memo, so only the first read after a write copies."""
        lat = self._materialized.get(key)
        if lat is not None:
            return lat
        group = self._key_group.get(key)
        if group is None:
            return None
        slab = self._slabs[group]
        row = slab.rows[key]
        if isinstance(slab, _DeviceSlab):
            clock, rank, flat = self._sync_row(slab, row)
            value = flat.reshape(slab.shape)
            ts = (clock, self.registry.node_id(rank))
        else:
            value = slab.vals[row].copy().reshape(slab.shape)
            ts = (int(slab.clocks[row, 0]),
                  self.registry.node_id(int(slab.nodes[row, 0])))
        lat = LWWLattice(ts, value)
        self._materialized[key] = lat
        self.materializations += 1
        return lat

    @staticmethod
    def _sync_row(slab: "_DeviceSlab",
                  row: int) -> Tuple[int, int, np.ndarray]:
        """Pull one device row to host: exactly ONE transfer (the triple
        device_gets together), counted against the slab's telemetry."""
        import jax

        from ..kernels import ops

        row_planes = ops.slab_row(slab.vals, slab.clocks, slab.nodes, row)
        t0 = time.perf_counter()
        flat, clock, rank = jax.device_get(row_planes)
        slab.xfer.sync_s += time.perf_counter() - t0
        slab.xfer.device_syncs += 1
        slab.xfer.d2h_bytes += flat.nbytes + 8
        return int(clock), int(rank), np.asarray(flat)

    def clear_memo(self) -> None:
        """Drop memoized registers (benchmarks model cold object reads)."""
        self._materialized.clear()

    def row_of(self, key: str) -> Optional[Tuple[int, int, np.ndarray]]:
        """(clock, rank, flat-view) of the stored row — no copy on the
        host tier; a counted one-transfer sync on the device tier (hot
        device paths resolve rows in bulk instead of calling this)."""
        group = self._key_group.get(key)
        if group is None:
            return None
        slab = self._slabs[group]
        row = slab.rows[key]
        if isinstance(slab, _DeviceSlab):
            return self._sync_row(slab, row)
        return (int(slab.clocks[row, 0]), int(slab.nodes[row, 0]),
                slab.vals[row])

    def delete(self, key: str) -> bool:
        group = self._key_group.pop(key, None)
        if group is None:
            return False
        self._slabs[group].drop(key)
        self._materialized.pop(key, None)
        self.layout_version += 1
        if self._watches:
            _note_removed(self._watches, (key,))
        return True

    # -- the plane wire format -------------------------------------------------
    def export_planes(self, keys: Sequence[str]) -> PlaneBatch:
        """Snapshot stored rows for ``keys`` into a :class:`PlaneBatch`.

        One vectorized gather per slab group; keys not resident in the
        arena are skipped (``MergeEngine.export_planes`` adds fallback
        entries to the sidecar).  Node planes hold registry ranks, so the
        batch's intern table is the registry's current id list — the
        receiver translates back through ids, never raw ranks.
        """
        batch = PlaneBatch(self.registry._ids)
        by_group: Dict[_GroupKey, List[str]] = {}
        for key in keys:
            group = self._key_group.get(key)
            if group is not None:
                by_group.setdefault(group, []).append(key)
        for group, ks in by_group.items():
            slab = self._slabs[group]
            if isinstance(slab, _DeviceSlab):
                from ..kernels import ops

                # one fused gather launch; the planes STAY device-side
                # (in-process gossip never syncs — the receiving arena
                # ingests them directly; real wire transfer goes through
                # PlaneBatch.to_host, the counted edge)
                rows = np.asarray([slab.rows[k] for k in ks], np.int32)
                vals, clocks, nodes = ops.slab_gather(
                    slab.vals, slab.clocks, slab.nodes, rows)
                batch.groups[group] = PlaneGroup(
                    slab.shape, slab.dtype, ks, vals, clocks, nodes)
                continue
            rows = np.asarray([slab.rows[k] for k in ks], np.int64)
            span = _contiguous_span(rows)
            if span is not None:  # steady-state layout: slice copies
                vals = slab.vals[span[0]:span[1]].copy()
                clocks = slab.clocks[span[0]:span[1]].copy()
                nodes = slab.nodes[span[0]:span[1]].copy()
            else:
                vals = slab.vals[rows]
                clocks = slab.clocks[rows]
                nodes = slab.nodes[rows]
            batch.groups[group] = PlaneGroup(
                slab.shape, slab.dtype, ks, vals, clocks, nodes)
        return batch

    def bulk_write(self, group: _GroupKey, keys: Sequence[str],
                   clocks: np.ndarray, ranks: np.ndarray,
                   vals: np.ndarray) -> None:
        """Vectorized multi-row overwrite: per-key work is dict upkeep
        only; the payload/clock/rank planes land as three scatters."""
        slab = self._slabs[group]
        rows = np.empty(len(keys), np.int64)
        bumped = False
        if self._watches:
            kg = self._key_group
            fresh = [k for k in keys if k not in kg]
            if fresh:
                _note_added(self._watches, fresh)
        for i, key in enumerate(keys):
            prev = self._key_group.get(key)
            if prev is not None and prev != group:
                self._slabs[prev].drop(key)
            if prev != group:
                bumped = True
            rows[i] = slab._alloc(key)
            self._key_group[key] = group
            self._materialized.pop(key, None)
        if bumped:
            self.layout_version += 1
        if isinstance(slab, _DeviceSlab):
            slab.write_rows(rows, clocks, ranks, vals)
            return
        slab.vals[rows] = vals
        slab.clocks[rows] = clocks
        slab.nodes[rows] = ranks

    def scatter_existing(self, group: _GroupKey, keys: Sequence[str],
                         rows: np.ndarray, clocks: np.ndarray,
                         ranks: np.ndarray, vals: np.ndarray) -> None:
        """Steady-state write-back: every key already lives at ``rows`` in
        this slab, so the update is three scatters and (only if a reader
        memoized something) memo invalidation."""
        slab = self._slabs[group]
        if isinstance(slab, _DeviceSlab):
            slab.write_rows(rows, clocks, ranks, vals)
        else:
            slab.vals[rows] = vals
            slab.clocks[rows] = clocks
            slab.nodes[rows] = ranks
        if self._materialized:
            for key in keys:
                self._materialized.pop(key, None)

    def rows_for_ingest(self, group: _GroupKey,
                        keys: Sequence[str]) -> Tuple[np.ndarray, np.ndarray]:
        """Target rows for a device-tier ingest: every key gets a row
        (unseen keys allocate), ``has`` marks the ones that already had
        a stored value.  Host-side dict upkeep only — the payload merge
        happens in one fused launch against these rows."""
        slab = self._slabs[group]
        kk = len(keys)
        rows = np.empty(kk, np.int32)
        has = np.zeros(kk, bool)
        fresh = False
        for i, key in enumerate(keys):
            row = slab.rows.get(key)
            if row is None:
                row = slab._alloc(key)
                self._key_group[key] = group
                fresh = True
            else:
                has[i] = True
            rows[i] = row
        if fresh:
            self.layout_version += 1
            if self._watches:
                _note_added(self._watches,
                            [k for k, h in zip(keys, has) if not h])
        if self._materialized:
            for key in keys:
                self._materialized.pop(key, None)
        return rows, has


# ---------------------------------------------------------------------------
# The merge engine: batched tensor plane + per-key fallback
# ---------------------------------------------------------------------------


class LatticeStore(MutableMapping):
    """Dict-like view over a MergeEngine (arena ∪ fallback).

    ``store[key] = lattice`` is a raw overwrite (matching the dict it
    replaces); merging goes through ``MergeEngine.merge_one/merge_batch``.
    """

    __slots__ = ("_engine",)

    def __init__(self, engine: "MergeEngine"):
        self._engine = engine

    def __getitem__(self, key: str) -> Lattice:
        value = self._engine.get(key)
        if value is None:
            raise KeyError(key)
        return value

    def __setitem__(self, key: str, value: Lattice) -> None:
        self._engine.set(key, value)

    def __delitem__(self, key: str) -> None:
        if not self._engine.delete(key):
            raise KeyError(key)

    def __iter__(self):
        yield from self._engine.fallback
        yield from self._engine.arena.keys()

    def __len__(self) -> int:
        return len(self._engine.fallback) + len(self._engine.arena)

    def __contains__(self, key) -> bool:  # avoid __getitem__ materialization
        return key in self._engine.fallback or key in self._engine.arena


class _ReduceGroupPlan:
    """One slab group's share of a replica-reduce plan: candidate
    (slab, rows, span) segments plus the prebuilt (Rp, K) index matrix.
    Slab objects are held by reference — row contents are re-gathered at
    execute, so a cached plan always reduces the newest planes."""

    __slots__ = ("group", "keys", "segs", "idx", "R", "device",
                 "idx_dev", "rows32", "dev_slabs")

    def __init__(self, group: _GroupKey, keys: List[str],
                 segs: list, idx: np.ndarray, R: int):
        self.group = group
        self.keys = keys
        self.segs = segs
        self.idx = idx
        self.R = R
        self.device = bool(segs) and all(
            isinstance(s, _DeviceSlab) for s, _, _ in segs)
        self.idx_dev: Optional[np.ndarray] = None
        self.rows32: Optional[List[np.ndarray]] = None
        self.dev_slabs: Optional[List[_DeviceSlab]] = None


class _ReducePlan:
    """Reusable structure half of ``reduce_replica_planes`` (see
    ``MergeEngine.plan_replica_reduce``)."""

    __slots__ = ("leftover", "groups")

    def __init__(self, leftover: List[str],
                 groups: List[_ReduceGroupPlan]):
        self.leftover = leftover
        self.groups = groups


class MergeEngine:
    """Routes lattice merges: tensor-LWW traffic through the batched
    kernels, everything else through per-key ``Lattice.merge``."""

    def __init__(self, registry: Optional[NodeRegistry] = None,
                 device: Optional[bool] = None):
        self.registry = registry if registry is not None else NodeRegistry()
        self.arena = LatticeArena(self.registry, device=device)
        self.device = self.arena.device
        self.fallback: Dict[str, Lattice] = {}
        # fallback *membership* version: read plans depend on which keys
        # are fallback-held, not on their values
        self._fb_version = 0
        self.view = LatticeStore(self)
        # telemetry: how much traffic actually batched
        self.launches = 0
        self.batched_keys = 0
        self.fallback_merges = 0
        # plane-ingest telemetry: packed rows applied without per-key
        # objects, and rows that had to materialize one (fallback-held
        # key or cross-group shape change) — zero in steady state
        self.plane_keys = 0
        self.plane_object_fallbacks = 0
        # read-plane telemetry: keys answered by reduce_replica_planes
        # (packed R-replica read-repair, no per-key objects)
        self.plane_reads = 0

    # -- device-tier telemetry / versioning --------------------------------
    @property
    def h2d_bytes(self) -> int:
        return self.arena.h2d_bytes

    @property
    def d2h_bytes(self) -> int:
        return self.arena.d2h_bytes

    @property
    def device_syncs(self) -> int:
        return self.arena.device_syncs

    @property
    def device_sync_s(self) -> float:
        return self.arena.device_sync_s

    def reset_transfer_stats(self) -> None:
        self.arena.reset_transfer_stats()

    @property
    def layout_version(self) -> int:
        """Bumps when the key -> row layout or fallback membership
        changes; cached read plans revalidate against it."""
        return self.arena.layout_version + self._fb_version

    # -- point ops -------------------------------------------------------------
    def get(self, key: str) -> Optional[Lattice]:
        value = self.fallback.get(key)
        if value is not None:
            return value
        return self.arena.get(key)

    def set(self, key: str, value: Lattice) -> None:
        # a key moving between fallback and arena is noted last as added
        # (by arena.set, or below once arena.delete noted it removed)
        if is_arena_lww(value):
            if self.fallback.pop(key, None) is not None:
                self._fb_version += 1
            self.arena.set(key, value)
        else:
            self.arena.delete(key)
            if key not in self.fallback:
                self._fb_version += 1
                if self.arena._watches:
                    _note_added(self.arena._watches, (key,))
            self.fallback[key] = value

    def delete(self, key: str) -> bool:
        if self.fallback.pop(key, None) is not None:
            self._fb_version += 1
            if self.arena._watches:
                _note_removed(self.arena._watches, (key,))
            return True
        return self.arena.delete(key)

    # -- key membership watches ------------------------------------------------
    def watch_keys(self) -> KeyWatch:
        """Start recording the keys this engine gains and loses; the
        caller drains the returned watch and drops it with
        :meth:`unwatch_keys`."""
        watch = KeyWatch()
        self.arena._watches.append(watch)
        return watch

    def unwatch_keys(self, watch: KeyWatch) -> None:
        watches = self.arena._watches
        if watch in watches:
            watches.remove(watch)

    def merge_one(self, key: str, value: Lattice) -> Lattice:
        """Per-key merge — the semantics the batched plane must match."""
        cur = self.get(key)
        merged = value if cur is None else cur.merge(value)
        self.fallback_merges += cur is not None
        self.set(key, merged)
        return merged

    # -- the batched plane ------------------------------------------------------
    def merge_batch(self, items: Sequence[Tuple[str, Lattice]]) -> int:
        """Apply a batch of (key, lattice) merges.

        Tensor-valued LWW entries coalesce into one
        ``ops.lww_merge_many`` launch per payload group; keys touching
        the fallback store (opaque payloads, non-LWW lattices, or a
        mid-batch payload-shape change) merge per-key in item order.
        Results are order-independent either way (merge is ACI).
        """
        per_key: Dict[str, List[Tuple[str, Lattice]]] = {}
        ineligible: Dict[str, bool] = {}
        for key, value in items:
            per_key.setdefault(key, []).append((key, value))
            if not is_arena_lww(value) or key in self.fallback:
                ineligible[key] = True
        groups: Dict[_GroupKey, Dict[str, List[LWWLattice]]] = {}
        for key, kv_items in per_key.items():
            if not ineligible.get(key):
                cands = [v for _, v in kv_items]
                group = self.arena.group_of(tensor_payload(cands[0].value))
                stored = self.arena.group_key_of(key)
                if all(self.arena.group_of(tensor_payload(v.value)) == group
                       for v in cands[1:]) and stored in (None, group):
                    groups.setdefault(group, {})[key] = cands
                    continue
            for k, v in kv_items:  # payload changed shape/dtype: python path
                self.merge_one(k, v)
        for group, keyed in groups.items():
            self._launch_group(group, keyed)
        return len(items)

    def _launch_group(self, group: _GroupKey,
                      keyed: Dict[str, List[LWWLattice]]) -> None:
        from ..kernels import ops  # deferred: keep core importable sans jax

        node_ids = [lat.timestamp[1] for cands in keyed.values()
                    for lat in cands]
        self.registry.ensure(node_ids)  # before reading stored ranks
        sample = tensor_payload(next(iter(keyed.values()))[0].value)
        slab = self.arena.slab_for(group, sample)
        D = slab.dim

        if isinstance(slab, _DeviceSlab):
            # device tier: per-key row_of syncs would serialize on the
            # PCIe bus — pack the candidates as one incoming plane group
            # (duplicates express multi-candidate keys) and run the same
            # fused device ingest the gossip path uses; fold order is
            # stored-first then item order, identical to the host pack
            keys_flat: List[str] = []
            clocks_l: List[int] = []
            ranks_l: List[int] = []
            flats: List[np.ndarray] = []
            for key, cands in keyed.items():
                for lat in cands:
                    keys_flat.append(key)
                    clocks_l.append(lat.timestamp[0])
                    ranks_l.append(self.registry.rank(lat.timestamp[1]))
                    flats.append(tensor_payload(lat.value).reshape(-1))
            pg = PlaneGroup(
                slab.shape, slab.dtype, keys_flat,
                np.stack(flats).astype(slab.dtype, copy=False),
                np.asarray(clocks_l, np.int32).reshape(-1, 1),
                np.asarray(ranks_l, np.int32).reshape(-1, 1))
            self._device_ingest(group, pg, slab, pg.node_idx)
            return

        candidates: List[List[Tuple[int, int, np.ndarray]]] = []
        keys = list(keyed)
        for key in keys:
            cands = [
                (lat.timestamp[0], self.registry.rank(lat.timestamp[1]),
                 tensor_payload(lat.value).reshape(-1))
                for lat in keyed[key]
            ]
            stored = self.arena.row_of(key)
            if stored is not None:
                cands.insert(0, stored)  # fold starts from the stored value
            candidates.append(cands)

        if self.arena._watches:
            fresh = [k for k in keys if k not in self.arena]
            if fresh:
                _note_added(self.arena._watches, fresh)
        R = max(len(c) for c in candidates)
        if R == 1:  # nothing to merge against: plain insert
            for key, cands in zip(keys, candidates):
                clock, rank, flat = cands[0]
                self.arena.set_raw(key, group, clock, rank, flat)
            return

        K = len(keys)
        Rp, Kp, Dp = _bucket(R, 2), _k_bucket(K), _bucket(D, 128)
        clocks = np.zeros((Rp, Kp, 1), np.int32)
        nodes = np.zeros((Rp, Kp, 1), np.int32)
        vals = np.zeros((Rp, Kp, Dp), slab.dtype)
        for j, cands in enumerate(candidates):
            for r in range(Rp):
                clock, rank, flat = cands[r] if r < len(cands) else cands[0]
                clocks[r, j, 0] = clock
                nodes[r, j, 0] = rank
                vals[r, j, :D] = flat

        win_val, win_clock, win_node = ops.lww_merge_many(clocks, nodes, vals)
        win_val = np.asarray(win_val)
        win_clock = np.asarray(win_clock)
        win_node = np.asarray(win_node)
        for j, key in enumerate(keys):
            self.arena.set_raw(key, group, int(win_clock[j, 0]),
                               int(win_node[j, 0]), win_val[j, :D])
        self.launches += 1
        self.batched_keys += K

    # -- the plane wire format: packed export / ingest ---------------------------
    def export_planes(self, keys: Sequence[str]) -> PlaneBatch:
        """Pack stored values for ``keys`` for arena-to-arena transfer:
        arena rows gather into planes, fallback entries ride the sidecar
        (existing object references — nothing new is constructed)."""
        batch = self.arena.export_planes(keys)
        if self.fallback:
            for key in keys:
                value = self.fallback.get(key)
                if value is not None:
                    batch.sidecar.append((key, value))
        return batch

    def ingest_planes(self, batch: PlaneBatch,
                      include_sidecar: bool = True) -> int:
        """Merge a packed batch in: one ``ops.lww_merge_many`` launch per
        slab group against the stored rows, vectorized gather/scatter on
        either side, zero per-key lattice objects for packed traffic.

        Sidecar entries keep exact per-key ``Lattice.merge`` semantics
        (callers that need special sidecar routing — the causal-cut
        cache — pass ``include_sidecar=False`` and handle them).
        Returns the number of items applied.
        """
        applied = 0
        if batch.groups and batch.node_ids:
            # intern sender ids first: a remap may rewrite stored planes,
            # and must happen before any rank is read below
            self.registry.ensure(batch.node_ids)
        for group, pg in batch.groups.items():
            applied += self._ingest_group(group, pg, batch.node_ids)
        if include_sidecar:
            for key, value in batch.sidecar:
                self.merge_one(key, value)
                applied += 1
        return applied

    def migrate_device(self, device: bool) -> PlaneBatch:
        """Move this engine's arena between the host-numpy and the
        device-resident slab tier.

        The whole arena exports as one packed :class:`PlaneBatch`
        (fused ``slab_gather`` per group on the device side), a fresh
        arena of the target mode is built, and the batch re-ingests
        through the empty-arena bulk-write scatter — per-key lattice
        objects are never constructed.  Demotion pulls planes down
        through the counted ``PlaneBatch.to_host`` edge before the swap
        so every byte shows on the transfer ledger.  Returns the moved
        batch (empty when already on the requested tier); the fallback
        dict is tier-independent and stays put.
        """
        if bool(device) == self.arena.device:
            return PlaneBatch(self.registry._ids)
        keys = list(self.arena.keys())
        batch = self.arena.export_planes(keys)
        if not device:
            batch = batch.to_host(self.arena._xfer)
        old = self.arena
        self.arena = LatticeArena(self.registry, device=device)
        # keep one transfer ledger across the swap: slabs capture the
        # stats object at creation, so this must precede any ingest
        self.arena._xfer = old._xfer
        # strictly advance past the old arena: cached read plans hold
        # refs into the retired slabs and must revalidate
        self.arena.layout_version = old.layout_version + 1
        self.device = self.arena.device
        self.ingest_planes(batch, include_sidecar=False)
        # the same keys, on another tier: no membership change to note
        self.arena._watches = old._watches
        return batch

    def _ingest_group(self, group: _GroupKey, pg: PlaneGroup,
                      node_ids: List[str]) -> int:
        K = len(pg)
        if K == 0:
            return 0
        rank_of = np.asarray([self.registry.rank(n) for n in node_ids]
                             or [0], np.int32)
        if _is_device(pg.node_idx):  # translate on device: no implicit sync
            import jax.numpy as jnp

            ranks = jnp.take(jnp.asarray(rank_of), pg.node_idx[:, 0])
        else:
            ranks = rank_of[pg.node_idx[:, 0]]
        # rows the planes cannot merge in place — a fallback-held key or a
        # cross-group shape/dtype change — take the exact per-key path
        kg = self.arena._key_group
        fb = self.fallback
        if fb:
            bad = [i for i, k in enumerate(pg.keys)
                   if k in fb or kg.get(k, group) != group]
        else:
            bad = [i for i, k in enumerate(pg.keys)
                   if kg.get(k, group) != group]
        if bad:
            self.plane_object_fallbacks += len(bad)
            bad_pg = pg.take(bad)
            if bad_pg.is_device():  # the exact path is host-side: one sync
                t0 = time.perf_counter()
                bad_pg = bad_pg.to_host()
                self.arena._xfer.sync_s += time.perf_counter() - t0
                self.arena._xfer.device_syncs += 1
                self.arena._xfer.d2h_bytes += bad_pg.vals.nbytes
            for i, key in enumerate(bad_pg.keys):
                ts = (int(bad_pg.clocks[i, 0]),
                      node_ids[int(bad_pg.node_idx[i, 0])])
                self.merge_one(key, LWWLattice(
                    ts, bad_pg.vals[i].copy().reshape(bad_pg.shape)))
            if len(bad) == K:
                return K
            kept = set(bad)
            eligible = [i for i in range(K) if i not in kept]
            ranks = ranks[np.asarray(eligible, np.int64)]
            pg = pg.take(eligible)
        kk = len(pg)
        slab = self.arena.slab_for_meta(group, pg.shape, pg.dtype)
        ranks_in = ranks.reshape(-1, 1)
        self.plane_keys += kk
        if isinstance(slab, _DeviceSlab):
            self._device_ingest(group, pg, slab, ranks_in)
            return K
        if len(set(pg.keys)) != kk:
            # duplicate keys (several gossip rounds queued): general
            # R-candidate packing, still ONE launch for the group
            self._ingest_group_multi(group, pg, slab, ranks_in)
            return K
        rows_of = slab.rows
        stored_list = [rows_of.get(k, -1) for k in pg.keys]
        all_stored = -1 not in stored_list
        stored_rows = np.asarray(stored_list, np.int64)
        span: Optional[Tuple[int, int]] = None
        if all_stored:
            # stored candidate first: full-timestamp ties keep the stored
            # row, exactly like the per-key fold (acc.merge(incoming)).
            # Contiguous rows (the steady-state layout: replicas insert
            # keys in the same order) read as zero-copy slices.
            span = _contiguous_span(stored_rows)
            if span is not None:
                a_clocks = slab.clocks[span[0]:span[1]]
                a_nodes = slab.nodes[span[0]:span[1]]
                a_vals = slab.vals[span[0]:span[1]]
            else:
                a_clocks = slab.clocks[stored_rows]
                a_nodes = slab.nodes[stored_rows]
                a_vals = slab.vals[stored_rows]
        else:
            has_stored = stored_rows >= 0
            if not has_stored.any():
                self.arena.bulk_write(group, pg.keys, pg.clocks, ranks_in,
                                      pg.vals)
                return K
            # keys with no stored row pad the stored candidate with the
            # incoming row — merge is idempotent, the winner is unchanged
            take = np.where(has_stored, stored_rows, 0)
            mask = has_stored[:, None]
            a_clocks = np.where(mask, slab.clocks[take], pg.clocks)
            a_nodes = np.where(mask, slab.nodes[take], ranks_in)
            a_vals = np.where(mask, slab.vals[take], pg.vals)

        from ..kernels import ops  # deferred: keep core importable sans jax

        D = slab.dim
        Kp, Dp = _k_bucket(kk), _bucket(D, 128)
        if Kp == kk and Dp == D:
            # aligned: pairwise launch straight off the gathered planes —
            # no (2, K, D) stacking, no padding copies
            win_val, win_clock, win_node = ops.lww_merge(
                a_clocks, a_nodes, a_vals, pg.clocks, ranks_in, pg.vals)
        else:
            pads = []
            for arr, cols in ((a_clocks, 1), (a_nodes, 1), (a_vals, Dp),
                              (pg.clocks, 1), (ranks_in, 1), (pg.vals, Dp)):
                padded = np.zeros((Kp, cols), arr.dtype)
                padded[:kk, : arr.shape[1]] = arr
                pads.append(padded)
            win_val, win_clock, win_node = ops.lww_merge(*pads)
        win_clock = np.asarray(win_clock)[:kk]
        win_node = np.asarray(win_node)[:kk]
        win_val = np.asarray(win_val)[:kk, :D].astype(slab.dtype, copy=False)
        if span is not None:  # contiguous: three slice assigns
            slab.vals[span[0]:span[1]] = win_val
            slab.clocks[span[0]:span[1]] = win_clock
            slab.nodes[span[0]:span[1]] = win_node
            if self.arena._materialized:
                for key in pg.keys:
                    self.arena._materialized.pop(key, None)
        elif all_stored:
            self.arena.scatter_existing(group, pg.keys, stored_rows,
                                        win_clock, win_node, win_val)
        else:
            self.arena.bulk_write(group, pg.keys, win_clock, win_node,
                                  win_val)
        self.launches += 1
        self.batched_keys += kk
        return K

    def _ingest_group_multi(self, group: _GroupKey, pg: PlaneGroup,
                            slab: _Slab, ranks_in: np.ndarray) -> None:
        """R-candidate ingest for batches carrying duplicate keys: pool =
        [incoming rows; touched stored rows], an (R, U) index matrix
        gathers candidates per unique key (stored first, then delivery
        order; short keys pad with their first candidate — idempotent)."""
        kk = len(pg)
        order: Dict[str, int] = {}
        cands: List[List[int]] = []
        for i, key in enumerate(pg.keys):
            j = order.get(key)
            if j is None:
                order[key] = len(cands)
                cands.append([i])
            else:
                cands[j].append(i)
        ukeys = list(order)
        U = len(ukeys)
        stored_take: List[int] = []
        for j, key in enumerate(ukeys):
            row = slab.rows.get(key)
            if row is not None:
                cands[j].insert(0, kk + len(stored_take))
                stored_take.append(row)
        pool_vals, pool_clocks, pool_nodes = pg.vals, pg.clocks, ranks_in
        if stored_take:
            take = np.asarray(stored_take, np.int64)
            pool_vals = np.concatenate([pool_vals, slab.vals[take]])
            pool_clocks = np.concatenate([pool_clocks, slab.clocks[take]])
            pool_nodes = np.concatenate([pool_nodes, slab.nodes[take]])
        R = max(len(c) for c in cands)
        idx = np.empty((R, U), np.int64)
        for j, c in enumerate(cands):
            idx[:, j] = [c[r] if r < len(c) else c[0] for r in range(R)]
        D = slab.dim
        Rp, Kp, Dp = _bucket(R, 2), _k_bucket(U), _bucket(D, 128)
        clocks = np.zeros((Rp, Kp, 1), np.int32)
        nodes = np.zeros((Rp, Kp, 1), np.int32)
        vals = np.zeros((Rp, Kp, Dp), slab.dtype)
        clocks[:R, :U] = pool_clocks[idx]
        nodes[:R, :U] = pool_nodes[idx]
        vals[:R, :U, :D] = pool_vals[idx]
        for r in range(R, Rp):  # replica padding: first candidate again
            clocks[r, :U] = clocks[0, :U]
            nodes[r, :U] = nodes[0, :U]
            vals[r, :U] = vals[0, :U]
        self._launch_planes(group, ukeys, slab, clocks, nodes, vals)

    def _launch_planes(self, group: _GroupKey, keys: Sequence[str],
                       slab: _Slab, clocks: np.ndarray, nodes: np.ndarray,
                       vals: np.ndarray) -> None:
        from ..kernels import ops  # deferred: keep core importable sans jax

        kk, D = len(keys), slab.dim
        win_val, win_clock, win_node = ops.lww_merge_many(clocks, nodes, vals)
        self.arena.bulk_write(
            group, keys,
            np.asarray(win_clock)[:kk], np.asarray(win_node)[:kk],
            np.asarray(win_val)[:kk, :D].astype(slab.dtype, copy=False))
        self.launches += 1
        self.batched_keys += kk

    # -- device-tier ingest: donated fused gather/merge/scatter ------------------
    def _device_ingest(self, group: _GroupKey, pg: PlaneGroup,
                       slab: _DeviceSlab, ranks_in) -> None:
        """Apply one group's rows to a device slab.  Row targets resolve
        host-side (dict bookkeeping only); the payload merge is ONE
        donated fused launch, so device-resident inputs (gossip between
        device engines) cross the host boundary zero times.  Branching
        — bulk insert vs pairwise merge vs duplicate-key multi-merge —
        mirrors the host path exactly, including the launch counters.
        """
        kk = len(pg)
        if len(set(pg.keys)) != kk:
            self._device_ingest_multi(group, pg, slab, ranks_in)
            return
        rows, has = self.arena.rows_for_ingest(group, pg.keys)
        if not has.any():  # nothing stored: overwrite scatter, no launch
            slab.write_rows(rows, pg.clocks, ranks_in, pg.vals)
            return
        slab.ingest_rows(rows, has, pg.clocks, ranks_in, pg.vals)
        self.launches += 1
        self.batched_keys += kk

    def _device_ingest_multi(self, group: _GroupKey, pg: PlaneGroup,
                             slab: _DeviceSlab, ranks_in) -> None:
        """Duplicate-key device ingest: same (R, U) candidate matrix as
        the host multi path (stored candidate first, then delivery
        order; padding repeats a candidate — idempotent), with the pool
        gather, merge and scatter fused into one donated launch."""
        kk = len(pg)
        order: Dict[str, int] = {}
        cands: List[List[int]] = []
        for i, key in enumerate(pg.keys):
            j = order.get(key)
            if j is None:
                order[key] = len(cands)
                cands.append([i])
            else:
                cands[j].append(i)
        ukeys = list(order)
        stored_take: List[int] = []
        for j, key in enumerate(ukeys):
            row = slab.rows.get(key)
            if row is not None:
                cands[j].insert(0, kk + len(stored_take))
                stored_take.append(row)
        R = max(len(c) for c in cands)
        U = len(ukeys)
        idx = np.empty((R, U), np.int32)
        for j, c in enumerate(cands):
            idx[:, j] = [c[r] if r < len(c) else c[0] for r in range(R)]
        urows, _ = self.arena.rows_for_ingest(group, ukeys)
        slab.ingest_multi(urows, idx, stored_take, pg.clocks, ranks_in,
                          pg.vals)
        self.launches += 1
        self.batched_keys += U

    # -- the read plane: batched R-replica read-repair reduction -----------------
    def reduce_replica_planes(
        self,
        keyed: Sequence[Tuple[str, Sequence["MergeEngine"]]],
    ) -> Tuple[PlaneBatch, List[str]]:
        """Reduce each key's replica rows to one winner — the batched
        read-repair read path (the symmetric twin of ``ingest_planes``).

        ``keyed`` pairs each (unique) key with its live replica engines
        in read order; every engine must share this engine's registry so
        stored node ranks are comparable.  Keys whose holding replicas
        all store them in their arenas under ONE slab group stack into an
        (R, K, D) candidate pile per group — payload movement is one
        vectorized gather per (replica slab, group) plus one
        fancy-indexed stack — and reduce with a single
        ``ops.lww_merge_many`` launch per group; candidate order per key
        is replica order, short keys pad by repeating their last
        candidate (any repeat is idempotent: the kernel keeps the
        earlier candidate on full-timestamp ties, so a duplicate can
        never displace a winner), so winners are bit-identical to the
        per-key ``Lattice.merge`` fold.  Winners come back as a
        :class:`PlaneBatch` whose node planes hold registry ranks
        (``node_ids`` is the registry id list): zero per-key lattice
        objects end-to-end.  On the device tier the whole pile —
        per-replica gathers, pool concat, candidate stack, reduction —
        is one fused jit per group and the winners stay on device.

        Returns ``(batch, leftover)``: leftover keys need the exact
        per-key object path (a replica holds the key in its fallback
        store, or replicas disagree on slab group); keys held by no
        replica appear in neither.

        Split as ``plan_replica_reduce`` (structure: rows + candidate
        indices) and ``execute_reduce_plan`` (value gathers + launches):
        callers with a stable topology cache the plan and re-execute it,
        skipping the per-key Python walk entirely.
        """
        return self.execute_reduce_plan(self.plan_replica_reduce(keyed))

    def plan_replica_reduce(
        self,
        keyed: Sequence[Tuple[str, Sequence["MergeEngine"]]],
    ) -> "_ReducePlan":
        """Structure half of ``reduce_replica_planes``: resolve each
        key's candidate (slab, row) refs and prebuild the per-group
        candidate index matrices, touching no value planes.

        The plan stays valid while the replica set and every involved
        engine's ``layout_version`` are unchanged; row *contents* are
        re-gathered at execute, so writes over existing keys never
        invalidate a cached plan.
        """
        leftover: List[str] = []
        # per group: keys + per-key candidate refs (pool id, local row pos)
        plans: Dict[_GroupKey, Tuple[List[str], List[List[Tuple[int, int]]]]] = {}
        # pool per (replica arena, group): rows gather once, vectorized
        pools: Dict[Tuple[int, _GroupKey], Tuple[_Slab, List[int]]] = {}
        for key, engines in keyed:
            group: Optional[_GroupKey] = None
            holders: List[MergeEngine] = []
            ok = True
            for eng in engines:
                if eng.registry is not self.registry:
                    raise ValueError(
                        "replica engines must share the reader's registry")
                if key in eng.fallback:
                    ok = False
                    break
                g = eng.arena._key_group.get(key)
                if g is None:
                    continue  # replica does not hold the key: fewer candidates
                if group is None:
                    group = g
                elif g != group:
                    ok = False  # replicas disagree on shape/dtype
                    break
                holders.append(eng)
            if not ok:
                leftover.append(key)
                continue
            if group is None:
                continue  # held nowhere: absent from the result
            cands: List[Tuple[int, int]] = []
            for eng in holders:
                slab = eng.arena._slabs[group]
                pool_id = (id(eng), group)
                pool = pools.get(pool_id)
                if pool is None:
                    pool = (slab, [])
                    pools[pool_id] = pool
                pool[1].append(slab.rows[key])
                cands.append((pool_id, len(pool[1]) - 1))
            plan = plans.get(group)
            if plan is None:
                plan = ([], [])
                plans[group] = plan
            plan[0].append(key)
            plan[1].append(cands)

        group_plans: List[_ReduceGroupPlan] = []
        for group, (keys, cand_refs) in plans.items():
            # candidate refs become global pool indices via per-segment
            # base offsets (segment order = pool insertion order)
            seg_ids = [pid for pid in pools if pid[1] == group]
            base: Dict[Tuple[int, _GroupKey], int] = {}
            off = 0
            for pid in seg_ids:
                base[pid] = off
                off += len(pools[pid][1])
            K = len(keys)
            R = max(len(c) for c in cand_refs)
            Rp = _bucket(R, 2)
            # (Rp, K) candidate index matrix, built vectorized: flat
            # per-key runs + cumsum starts; rows past a key's candidate
            # count clamp to a repeat candidate (idempotent padding —
            # the kernel keeps the earlier candidate on full-timestamp
            # ties, so duplicates can never displace a winner)
            flat = np.asarray([base[pid] + pos for c in cand_refs
                               for pid, pos in c], np.int64)
            counts = np.asarray([len(c) for c in cand_refs], np.int64)
            starts = np.cumsum(counts) - counts
            r_grid = np.arange(Rp, dtype=np.int64)[:, None]
            idx = flat[starts[None, :]
                       + np.minimum(r_grid, counts[None, :] - 1)]
            segs = []
            for pid in seg_ids:
                slab, row_list = pools[pid]
                rows = np.asarray(row_list, np.int64)
                span = _contiguous_span(rows) if len(rows) else None
                segs.append((slab, rows, span))
            gp = _ReduceGroupPlan(group, list(keys), segs, idx, R)
            if gp.device:
                # fused-jit form, shaped so the jit sees few programs:
                # segments in capacity order, each segment's rows padded
                # to a power-of-two bucket (pad lanes re-gather its first
                # row and are never indexed), and a K-bucketed index
                # matrix (pad columns repeat one candidate; winners
                # slice [:K])
                lens = np.asarray([len(r) for _, r, _ in segs], np.int64)
                old_base = np.cumsum(lens) - lens
                new_base = np.empty_like(old_base)
                order = sorted(range(len(segs)), key=lambda i: segs[i][0].cap)
                rows32, off = [], 0
                for i in order:
                    new_base[i] = off
                    rows = np.full(_bucket(int(lens[i]), 8), segs[i][1][0],
                                   np.int32)
                    rows[:lens[i]] = segs[i][1]
                    rows32.append(rows)
                    off += len(rows)
                seg_of = np.searchsorted(old_base, idx, side="right") - 1
                Kp = _k_bucket(K)
                idx_dev = np.empty((Rp, Kp), np.int32)
                idx_dev[:, :K] = idx + (new_base - old_base)[seg_of]
                idx_dev[:, K:] = idx_dev[0, 0]
                gp.idx_dev = idx_dev
                gp.rows32 = rows32
                gp.dev_slabs = [segs[i][0] for i in order]
            group_plans.append(gp)
        return _ReducePlan(leftover, group_plans)

    def execute_reduce_plan(
        self, plan: "_ReducePlan",
    ) -> Tuple[PlaneBatch, List[str]]:
        """Value half: gather candidate planes fresh (the newest row
        contents flow through a cached plan) and reduce each group with
        one launch — a single fused device jit when every segment slab
        is device-resident."""
        batch = PlaneBatch(self.registry._ids)
        for g in plan.groups:
            if g.device:
                self._reduce_group_device(batch, g)
            else:
                self._reduce_group_host(batch, g)
        return batch, list(plan.leftover)

    def _reduce_group_host(self, batch: PlaneBatch,
                           g: "_ReduceGroupPlan") -> None:
        gathered = []
        for slab, rows, span in g.segs:
            if span is not None:  # steady-state layout: zero-copy slices
                gathered.append((slab.clocks[span[0]:span[1]],
                                 slab.nodes[span[0]:span[1]],
                                 slab.vals[span[0]:span[1]]))
            else:
                gathered.append((slab.clocks[rows], slab.nodes[rows],
                                 slab.vals[rows]))
        if len(gathered) == 1:
            pool_clocks, pool_nodes, pool_vals = gathered[0]
        else:
            pool_clocks = np.concatenate([t[0] for t in gathered])
            pool_nodes = np.concatenate([t[1] for t in gathered])
            pool_vals = np.concatenate([t[2] for t in gathered])
        keys = g.keys
        K = len(keys)
        shape, _ = g.group
        slab_dtype = pool_vals.dtype
        D = pool_vals.shape[1]
        self.plane_reads += K
        if g.R == 1:  # single live candidate per key: a pure gather
            idx0 = g.idx[0]
            batch.groups[g.group] = PlaneGroup(
                shape, slab_dtype, list(keys), pool_vals[idx0],
                pool_clocks[idx0], pool_nodes[idx0])
            return

        from ..kernels import ops  # deferred: keep core importable sans jax

        Rp = g.idx.shape[0]
        Kp, Dp = _k_bucket(K), _bucket(D, 128)
        idx = g.idx
        if Kp == K and Dp == D:
            # bucket-aligned: the index gather IS the kernel input —
            # no zero staging, no second payload copy
            clocks = pool_clocks[idx]
            nodes = pool_nodes[idx]
            vals = pool_vals[idx]
        else:
            clocks = np.zeros((Rp, Kp, 1), np.int32)
            nodes = np.zeros((Rp, Kp, 1), np.int32)
            vals = np.zeros((Rp, Kp, Dp), slab_dtype)
            clocks[:, :K] = pool_clocks[idx]
            nodes[:, :K] = pool_nodes[idx]
            vals[:, :K, :D] = pool_vals[idx]
        win_val, win_clock, win_node = ops.lww_merge_many(
            clocks, nodes, vals)
        batch.groups[g.group] = PlaneGroup(
            shape, slab_dtype, list(keys),
            np.asarray(win_val)[:K, :D].astype(slab_dtype, copy=False),
            np.asarray(win_clock)[:K], np.asarray(win_node)[:K])
        self.launches += 1
        self.batched_keys += K

    def _reduce_group_device(self, batch: PlaneBatch,
                             g: "_ReduceGroupPlan") -> None:
        """The device read pile: gathers, concat, candidate stack and
        reduction fused into ``ops.slab_reduce``; winners stay on device
        (the host boundary is only crossed if a consumer materializes)."""
        from ..kernels import ops

        win_val, win_clock, win_node = ops.slab_reduce(
            [s.clocks for s in g.dev_slabs],
            [s.nodes for s in g.dev_slabs],
            [s.vals for s in g.dev_slabs],
            list(g.rows32), g.idx_dev)
        keys = g.keys
        K = len(keys)
        shape, _ = g.group
        self.plane_reads += K
        batch.groups[g.group] = PlaneGroup(
            shape, g.segs[0][0].dtype, list(keys),
            win_val[:K], win_clock[:K], win_node[:K])
        if g.R > 1:
            self.launches += 1
            self.batched_keys += K


# ---------------------------------------------------------------------------
# Batched R-replica reduction (the get_merged read-repair path)
# ---------------------------------------------------------------------------


def try_reduce_lww(lattices: Sequence[Lattice]) -> Optional[LWWLattice]:
    """Reduce R replica values of one key through ``ops.lww_merge_many``.

    Returns None when the replicas are not uniformly tensor-valued LWW
    registers of one shape/dtype (callers then fold ``Lattice.merge``).
    Node ranking is per-call (sorted ids), so no registry is needed and
    the tie-break still matches the string comparison exactly.
    """
    if len(lattices) < 2:
        return None
    arrays = []
    for lat in lattices:
        if not is_arena_lww(lat):
            return None
        arrays.append(tensor_payload(lat.value))
    shape, dtype = arrays[0].shape, arrays[0].dtype
    if any(a.shape != shape or a.dtype != dtype for a in arrays[1:]):
        return None

    from ..kernels import ops

    ids = sorted({lat.timestamp[1] for lat in lattices})
    rank = {nid: i for i, nid in enumerate(ids)}
    R = len(lattices)
    D = int(np.prod(shape)) if shape else 1
    Rp, Dp = _bucket(R, 2), _bucket(D, 128)
    clocks = np.zeros((Rp, 1, 1), np.int32)
    nodes = np.zeros((Rp, 1, 1), np.int32)
    vals = np.zeros((Rp, 1, Dp), dtype)
    for r in range(Rp):
        lat = lattices[r] if r < R else lattices[0]
        clocks[r, 0, 0] = lat.timestamp[0]
        nodes[r, 0, 0] = rank[lat.timestamp[1]]
        vals[r, 0, :D] = tensor_payload(lat.value).reshape(-1)
    win_val, win_clock, win_node = ops.lww_merge_many(clocks, nodes, vals)
    ts = (int(np.asarray(win_clock)[0, 0]), ids[int(np.asarray(win_node)[0, 0])])
    value = np.asarray(win_val)[0, :D].astype(dtype, copy=True).reshape(shape)
    return LWWLattice(ts, value)


# ---------------------------------------------------------------------------
# Batched vector-clock dominance (the causal-cut path)
# ---------------------------------------------------------------------------


def vc_classify_batch(
    pairs: Sequence[Tuple[VectorClock, VectorClock]],
) -> Tuple[np.ndarray, np.ndarray]:
    """Classify K (a, b) VectorClock pairs through ``ops.vc_join_classify``.

    Returns bool arrays (a_dominates_b, b_dominates_a) of length K.  The
    pairs are densified over the union of their node ids; missing entries
    are zero, exactly the VectorClock convention.
    """
    K = len(pairs)
    if K == 0:
        return np.zeros(0, bool), np.zeros(0, bool)
    ids = sorted({
        nid for a, b in pairs
        for nid in (*a.entries().keys(), *b.entries().keys())
    })
    col = {nid: i for i, nid in enumerate(ids)}
    Kp, Np = _k_bucket(K), _bucket(max(len(ids), 1), 8)
    mat_a = np.zeros((Kp, Np), np.int32)
    mat_b = np.zeros((Kp, Np), np.int32)
    for j, (a, b) in enumerate(pairs):
        for nid, v in a.entries().items():
            mat_a[j, col[nid]] = v
        for nid, v in b.entries().items():
            mat_b[j, col[nid]] = v

    from ..kernels import ops

    _, adom, bdom = ops.vc_join_classify(mat_a, mat_b)
    return (np.asarray(adom).reshape(-1)[:K].astype(bool),
            np.asarray(bdom).reshape(-1)[:K].astype(bool))


def vc_dominates_or_concurrent_batch(
    pairs: Sequence[Tuple[VectorClock, VectorClock]],
) -> np.ndarray:
    """For each (a, b): a.dominates(b) or a.concurrent_with(b).

    This is the causal-cut readability predicate
    (``CausalLattice.dominates_or_concurrent``): reading a cannot violate
    the dependency lower bound b.  With the classify flags it reduces to
    ``a_dom_b | ~b_dom_a`` (equal clocks dominate; only b strictly above
    a fails).
    """
    adom, bdom = vc_classify_batch(pairs)
    return adom | ~bdom
