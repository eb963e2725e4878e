"""Jitted public wrappers around the Pallas kernels, with custom VJPs.

On a real TPU these lower to ``pl.pallas_call`` Mosaic kernels; on CPU they
run the same kernel bodies under ``interpret=True`` (and fall back to the
pure-jnp reference for shapes the tiled kernels do not support).

Training needs gradients, and Pallas kernels are not differentiable, so
each trainable op carries a ``jax.custom_vjp``:

* ``flash_attention``: forward emits (o, lse); backward is the *flash
  backward* algorithm in pure JAX — a ``lax.scan`` over KV blocks using
  only (q, k, v, o, lse), so the (T, S) score matrix never materializes
  (activation memory stays O(T·Dh), which is what lets train_4k fit);
* ``rglru_scan``: the linear-recurrence adjoint is itself a linear
  recurrence run *backwards* — we reuse the same Pallas kernel on flipped
  inputs (G_t = g_t + a_{t+1} G_{t+1});
* ``ssd_scan``: backward differentiates a checkpointed chunked-jnp mirror
  of the kernel math — per-chunk recompute, O(T/L) saved states.

The model layers call *these* entry points, never the kernels directly.
``set_backend("reference")`` forces the oracle path (used when measuring
kernel-vs-XLA deltas in the perf loop).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from ..pshard import active_rules
from .decode_attention import decode_attention as _decode_kernel
from .flash_attention import flash_attention as _flash_kernel
from .lww_merge import lww_merge as _lww_kernel
from .lww_merge import lww_merge_many as _lww_many_kernel
from .rglru_scan import rglru_scan as _rglru_kernel
from .ssd_scan import ssd_scan as _ssd_kernel
from .vector_clock import causal_merge as _causal_merge_kernel
from .vector_clock import vc_join_classify as _vc_kernel

_BACKEND = "kernel"  # 'kernel' | 'reference'
NEG_INF = -1e30


def set_backend(name: str) -> None:
    global _BACKEND
    assert name in ("kernel", "reference"), name
    _BACKEND = name


def get_backend() -> str:
    return _BACKEND


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _shard_mapped(fn, arg_axes, out_axes, args):
    """Run a Pallas kernel per-shard under shard_map when rules are active.

    ``pallas_call`` is opaque to the SPMD partitioner — without this, XLA
    all-gathers every operand onto every chip (the dry-run showed 10.6 GB
    all-gathers per attention call).  Inside shard_map each device runs the
    kernel on its local block; specs come from the logical rules with
    divisibility fallback, so ragged dims just replicate.
    """
    rules = active_rules()
    if rules is None:
        return fn(*args)
    in_specs = tuple(
        rules.spec_for(ax, a.shape) for ax, a in zip(arg_axes, args)
    )
    out_shapes = jax.eval_shape(fn, *args)
    flat_out, treedef = jax.tree_util.tree_flatten(out_shapes)
    if isinstance(out_axes[0], (list, tuple)) and not isinstance(out_axes[0], str):
        flat_axes = list(out_axes)
    else:
        flat_axes = [out_axes]
    out_specs = treedef.unflatten(
        [rules.spec_for(ax, s.shape) for ax, s in zip(flat_axes, flat_out)]
    )
    return jax.shard_map(fn, mesh=rules.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


# ---------------------------------------------------------------------------
# lattice merges (no gradients)
#
# These are the data plane of the storage tier (core.arena.MergeEngine
# routes every batched merge here), not just benchmark entry points, so
# the off-TPU path must be fast: interpret-mode Pallas executes the
# kernel body per grid step in Python — a correctness harness, not a
# data plane.  Off TPU (or for unaligned shapes) we therefore run the
# jit-compiled jnp mirrors, which are the same math XLA-fused; the Mosaic
# kernels serve aligned shapes on real TPUs.  test_kernels still
# exercises the Pallas bodies directly under interpret=True.
#
# K-sharding: arena slab planes are partitioned along the key axis.
# With more than one local device the batched lattice ops run under
# shard_map over a 1-D "kvs" mesh (launch.mesh.make_merge_mesh): each
# device merges its local rows — the op is elementwise along K, so no
# collectives and the result is bit-identical to the single-device path,
# which is used unchanged when the mesh has one device (or K does not
# divide).  Growing K is then a mesh decision, not a rewrite.
# ---------------------------------------------------------------------------

from jax.sharding import PartitionSpec as P

_lww_merge_xla = jax.jit(ref.lww_merge_ref)
_lww_merge_many_xla = jax.jit(ref.lww_merge_many_ref)
_vc_join_classify_xla = jax.jit(ref.vc_join_classify_ref)
_causal_merge_xla = jax.jit(ref.causal_merge_ref)

_MERGE_MESH = {"mesh": None, "resolved": False}
_SHARDED_FNS = {}


def set_merge_mesh(mesh) -> None:
    """Set (or disable, with None) the K-sharding mesh for lattice ops."""
    _MERGE_MESH["mesh"] = mesh
    _MERGE_MESH["resolved"] = True
    _SHARDED_FNS.clear()


def merge_mesh():
    """The active 1-D merge mesh; auto-built from the local devices on
    first use (None — the unsharded path — for a single device)."""
    if not _MERGE_MESH["resolved"]:
        from ..launch.mesh import make_merge_mesh

        _MERGE_MESH["mesh"] = make_merge_mesh()
        _MERGE_MESH["resolved"] = True
    return _MERGE_MESH["mesh"]


def merge_mesh_size() -> int:
    mesh = merge_mesh()
    return 1 if mesh is None else mesh.size


def _lww_many_local(clocks, nodes, vals):
    """Per-device body: shapes here are local (post-partition)."""
    R, K, D = vals.shape
    if _BACKEND == "reference" or _interpret() or K % 8 != 0 or D % 128 != 0:
        return ref.lww_merge_many_ref(clocks, nodes, vals)
    return _lww_many_kernel(clocks, nodes, vals, interpret=False)


def _lww_pair_local(clock_a, node_a, val_a, clock_b, node_b, val_b):
    K, D = val_a.shape
    if _BACKEND == "reference" or _interpret() or K % 8 != 0 or D % 128 != 0:
        return ref.lww_merge_ref(clock_a, node_a, val_a, clock_b, node_b, val_b)
    return _lww_kernel(
        clock_a, node_a, val_a, clock_b, node_b, val_b, interpret=False
    )


def _vc_local(a, b):
    K, N = a.shape
    if _BACKEND == "reference" or _interpret() or K % 8 != 0:
        return ref.vc_join_classify_ref(a, b)
    return _vc_kernel(a, b, interpret=False)


def _k_sharded(name, body, mesh, in_specs, out_specs):
    key = (name, mesh, _BACKEND)
    fn = _SHARDED_FNS.get(key)
    if fn is None:
        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                   out_specs=out_specs, check_vma=False))
        _SHARDED_FNS[key] = fn
    return fn


def lww_merge(clock_a, node_a, val_a, clock_b, node_b, val_b):
    K, D = val_a.shape
    mesh = merge_mesh()
    if mesh is not None and K >= mesh.size and K % mesh.size == 0:
        fn = _k_sharded(
            "lww_pair", _lww_pair_local, mesh,
            in_specs=(P("kvs", None),) * 6,
            out_specs=(P("kvs", None),) * 3)
        return fn(clock_a, node_a, val_a, clock_b, node_b, val_b)
    if _BACKEND == "reference" or _interpret() or K % 8 != 0 or D % 128 != 0:
        return _lww_merge_xla(clock_a, node_a, val_a, clock_b, node_b, val_b)
    return _lww_kernel(
        clock_a, node_a, val_a, clock_b, node_b, val_b, interpret=False
    )


def lww_merge_many(clocks, nodes, vals):
    R, K, D = vals.shape
    mesh = merge_mesh()
    if mesh is not None and K >= mesh.size and K % mesh.size == 0:
        fn = _k_sharded(
            "lww_many", _lww_many_local, mesh,
            in_specs=(P(None, "kvs", None),) * 3,
            out_specs=(P("kvs", None), P("kvs", None), P("kvs", None)))
        return fn(clocks, nodes, vals)
    if _BACKEND == "reference" or _interpret() or K % 8 != 0 or D % 128 != 0:
        return _lww_merge_many_xla(clocks, nodes, vals)
    return _lww_many_kernel(clocks, nodes, vals, interpret=False)


def vc_join_classify(a, b):
    K, N = a.shape
    mesh = merge_mesh()
    if mesh is not None and K >= mesh.size and K % mesh.size == 0:
        fn = _k_sharded(
            "vc_classify", _vc_local, mesh,
            in_specs=(P("kvs", None),) * 2,
            out_specs=(P("kvs", None), P("kvs", None), P("kvs", None)))
        return fn(a, b)
    if _BACKEND == "reference" or _interpret() or K % 8 != 0:
        return _vc_join_classify_xla(a, b)
    return _vc_kernel(a, b, interpret=False)


def causal_merge(vc_a, val_a, vc_b, val_b):
    K, _ = vc_a.shape
    if _BACKEND == "reference" or _interpret() or K % 8 != 0:
        return _causal_merge_xla(vc_a, val_a, vc_b, val_b)
    return _causal_merge_kernel(vc_a, val_a, vc_b, val_b, interpret=False)


# ---------------------------------------------------------------------------
# device-resident slab tier
#
# With ``core.arena`` in device mode the slab planes themselves are jax
# arrays ((cap, D) values + (cap, 1) int32 clock/node planes, sharded
# along rows over the "kvs" mesh when capacities divide), and the ops
# below are the only things that touch them: donated jitted
# gather -> merge -> scatter fusions built on the SAME ``ref`` merge
# bodies as the host launches.  The merge is pure selection (int32
# predicate + where), so every winner is bit-identical to the host path
# and to the per-key ``LWWLattice.merge`` fold.
#
# Donation (`donate_argnums`) makes each update in place: the engine
# hands its slab buffers to the jit and keeps the returned ones, so
# steady-state ingest/read traffic allocates nothing host-side and never
# crosses the PCIe boundary.  Callers must treat passed-in planes as
# consumed (the arena reassigns them from the return value).
#
# Determinism at padded lanes: callers pad scatter row indices with the
# slab's scratch row (cap - 1, never key-mapped) and pad the incoming
# planes with zeros, so every duplicate scatter lane writes identical
# bytes — the result is well-defined even though XLA leaves the winning
# duplicate unspecified.
# ---------------------------------------------------------------------------


def _take_rows(plane, rows):
    """``jnp.take(plane, rows, axis=0)``, bit-exact on a row-sharded plane.

    The SPMD partitioner gathers from a sharded operand by summing every
    shard's masked local gather, and -0.0 + 0.0 is +0.0: float payloads
    would lose the sign of zero (and NaN payload bits).  Gathering the
    integer bit pattern is exact."""
    if not jnp.issubdtype(plane.dtype, jnp.floating):
        return jnp.take(plane, rows, axis=0)
    bits = jnp.dtype(f"uint{8 * plane.dtype.itemsize}")
    return jax.lax.bitcast_convert_type(
        jnp.take(jax.lax.bitcast_convert_type(plane, bits), rows, axis=0),
        plane.dtype)


def slab_sharding(rows: int):
    """NamedSharding for a device slab of ``rows`` rows (None: unsharded)."""
    from ..launch.sharding import kvs_slab_sharding

    return kvs_slab_sharding(merge_mesh(), rows)


def slab_place(arr, rows: Optional[int] = None):
    """Put one slab plane on the device tier, row-sharded when eligible."""
    rows = arr.shape[0] if rows is None else rows
    sharding = slab_sharding(rows)
    if sharding is None:
        return jax.device_put(arr)
    return jax.device_put(arr, sharding)


def slab_zeros(rows: int, cols: int, dtype):
    return slab_place(jnp.zeros((rows, cols), dtype), rows)


def slab_grow(vals, clocks, nodes, new_rows: int):
    """Grow slab planes to ``new_rows`` (zero-padded) and re-place them —
    rare (amortized by doubling), so it is a plain copy, not donated."""
    out = []
    for arr in (vals, clocks, nodes):
        pad = ((0, new_rows - arr.shape[0]), (0, 0))
        out.append(slab_place(jnp.pad(arr, pad), new_rows))
    return tuple(out)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def slab_set_row(vals, clocks, nodes, row, clock, rank, flat):
    """Point overwrite of one row (arena.set / set_raw)."""
    return (vals.at[row].set(flat.astype(vals.dtype)),
            clocks.at[row, 0].set(clock),
            nodes.at[row, 0].set(rank))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def slab_move_row(vals, clocks, nodes, src, dst):
    """Copy row ``src`` over row ``dst`` (the swap-last delete)."""
    return (vals.at[dst].set(_take_rows(vals, src)),
            clocks.at[dst].set(clocks[src]),
            nodes.at[dst].set(nodes[src]))


@functools.partial(jax.jit, donate_argnums=(0,))
def slab_remap_nodes(nodes, remap):
    """Registry rank remap over the stored node plane."""
    return jnp.take(remap, nodes, axis=0).reshape(nodes.shape)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def slab_write_rows(vals, clocks, nodes, rows, in_clocks, in_nodes, in_vals):
    """Multi-row overwrite scatter (bulk_write / scatter_existing)."""
    return (vals.at[rows].set(in_vals.astype(vals.dtype)),
            clocks.at[rows].set(in_clocks),
            nodes.at[rows].set(in_nodes))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def slab_ingest_rows(vals, clocks, nodes, rows, has, in_clocks, in_nodes,
                     in_vals):
    """Fused pairwise plane ingest: gather stored rows, LWW-merge against
    the incoming planes (stored candidate first — full-timestamp ties
    keep the stored row, like the per-key fold), scatter winners back.

    ``rows`` must be a valid target row for every lane (callers allocate
    rows for unseen keys first); ``has`` masks lanes whose key had no
    stored value, which merge against themselves (idempotent).
    """
    a_clocks = jnp.where(has, jnp.take(clocks, rows, axis=0), in_clocks)
    a_nodes = jnp.where(has, jnp.take(nodes, rows, axis=0), in_nodes)
    a_vals = jnp.where(has, _take_rows(vals, rows),
                       in_vals.astype(vals.dtype))
    win_val, win_clock, win_node = ref.lww_merge_ref(
        a_clocks, a_nodes, a_vals,
        in_clocks, in_nodes, in_vals.astype(vals.dtype))
    return (vals.at[rows].set(win_val),
            clocks.at[rows].set(win_clock),
            nodes.at[rows].set(win_node))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def slab_ingest_multi(vals, clocks, nodes, urows, idx, stored_take,
                      in_clocks, in_nodes, in_vals):
    """Fused R-candidate ingest (duplicate keys in one batch): pool =
    [incoming rows; gathered stored rows], ``idx`` (R, U) gathers each
    unique key's candidates (stored first, then delivery order; padding
    repeats a candidate — idempotent), one many-way merge, scatter at
    ``urows``."""
    pool_clocks = jnp.concatenate(
        [in_clocks, jnp.take(clocks, stored_take, axis=0)])
    pool_nodes = jnp.concatenate(
        [in_nodes, jnp.take(nodes, stored_take, axis=0)])
    pool_vals = jnp.concatenate(
        [in_vals.astype(vals.dtype), _take_rows(vals, stored_take)])
    win_val, win_clock, win_node = ref.lww_merge_many_ref(
        pool_clocks[idx], pool_nodes[idx], _take_rows(pool_vals, idx))
    return (vals.at[urows].set(win_val),
            clocks.at[urows].set(win_clock),
            nodes.at[urows].set(win_node))


@jax.jit
def slab_gather(vals, clocks, nodes, rows):
    """Row gather into fresh buffers (export snapshots: safe against the
    source slab's later donated updates)."""
    return (_take_rows(vals, rows), jnp.take(clocks, rows, axis=0),
            jnp.take(nodes, rows, axis=0))


@jax.jit
def slab_row(vals, clocks, nodes, row):
    """One row's (value, clock, rank) — the materialize edge; the caller
    device_gets the triple in a single transfer."""
    return _take_rows(vals, row), clocks[row, 0], nodes[row, 0]


@jax.jit
def slab_reduce(seg_clocks, seg_nodes, seg_vals, seg_rows, idx):
    """Fused R-replica read reduction: per-(replica, group) row gathers,
    pool concat, an (R, K) candidate gather, one many-way merge — the
    whole ``reduce_replica_planes`` pile as a single launch with the
    winners left on device.

    ``seg_*`` are equal-length lists (pytrees) of the replicas' slab
    planes and row-index arrays; ``idx`` indexes the concatenated pool
    in per-segment base order, padded with repeat candidates
    (idempotent).  Returns (val, clock, node) winner planes.
    """
    pool_clocks = jnp.concatenate(
        [jnp.take(c, r, axis=0) for c, r in zip(seg_clocks, seg_rows)])
    pool_nodes = jnp.concatenate(
        [jnp.take(n, r, axis=0) for n, r in zip(seg_nodes, seg_rows)])
    pool_vals = jnp.concatenate(
        [_take_rows(v, r) for v, r in zip(seg_vals, seg_rows)])
    return ref.lww_merge_many_ref(
        pool_clocks[idx], pool_nodes[idx], _take_rows(pool_vals, idx))


# ---------------------------------------------------------------------------
# flash attention with flash backward
# ---------------------------------------------------------------------------


def _attn_fwd_impl(q, k, v, causal, window, q_start, block_q, block_kv):
    B, Hq, T, Dh = q.shape
    S = k.shape[2]
    bt, bs = min(block_q, T), min(block_kv, S)
    if (_BACKEND == "reference" or T % bt != 0 or S % bs != 0):
        o = ref.attention_ref(q, k, v, causal=causal, window=window,
                              q_start=q_start)
        lse = _lse_ref(q, k, causal, window, q_start)
        return o, lse
    fn = functools.partial(
        _flash_kernel, causal=causal, window=window, q_start=q_start,
        block_q=bt, block_kv=bs, interpret=_interpret())
    return _shard_mapped(
        fn,
        arg_axes=[("batch", "heads", None, None),
                  ("batch", "kv_heads", None, None),
                  ("batch", "kv_heads", None, None)],
        out_axes=[("batch", "heads", None, None), ("batch", "heads", None)],
        args=(q, k, v),
    )


def _lse_ref(q, k, causal, window, q_start):
    B, Hq, T, Dh = q.shape
    _, Hkv, S, _ = k.shape
    kk = jnp.repeat(k, Hq // Hkv, axis=1)
    s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) / (Dh ** 0.5)
    mask = _attn_mask(T, S, causal, window, q_start)
    s = jnp.where(mask[None, None], s, NEG_INF)
    return jax.nn.logsumexp(s, axis=-1)


def _attn_mask(T, S, causal, window, q_start):
    rows = q_start + jnp.arange(T)[:, None]
    cols = jnp.arange(S)[None, :]
    mask = jnp.ones((T, S), dtype=bool)
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    return mask


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, q_start, block_q, block_kv):
    o, _ = _attn_fwd_impl(q, k, v, causal, window, q_start, block_q, block_kv)
    return o


def _flash_fwd(q, k, v, causal, window, q_start, block_q, block_kv):
    o, lse = _attn_fwd_impl(q, k, v, causal, window, q_start, block_q, block_kv)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, window, q_start, block_q, block_kv, res, g):
    """Flash backward: lax.scan over KV blocks; O(T*Dh) live memory."""
    q, k, v, o, lse = res
    B, Hq, T, Dh = q.shape
    _, Hkv, S, _ = k.shape
    group = Hq // Hkv
    scale = 1.0 / (Dh ** 0.5)
    bs = min(block_kv, S)
    if S % bs != 0:
        bs = S
    nblk = S // bs
    q32 = q.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    o32 = o.astype(jnp.float32)
    delta = jnp.sum(g32 * o32, axis=-1)  # (B,Hq,T)
    qg = q32.reshape(B, Hkv, group, T, Dh)
    gg = g32.reshape(B, Hkv, group, T, Dh)
    lse_g = lse.reshape(B, Hkv, group, T)
    delta_g = delta.reshape(B, Hkv, group, T)
    kb = k.reshape(B, Hkv, nblk, bs, Dh).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, Hkv, nblk, bs, Dh).transpose(2, 0, 1, 3, 4)
    rows = q_start + jnp.arange(T)

    def body(dq_acc, inputs):
        j, k_blk, v_blk = inputs  # (B,Hkv,bs,Dh)
        k32 = k_blk.astype(jnp.float32)
        v32 = v_blk.astype(jnp.float32)
        s = jnp.einsum("bkgtd,bksd->bkgts", qg, k32) * scale
        cols = j * bs + jnp.arange(bs)
        mask = jnp.ones((T, bs), bool)
        if causal:
            mask &= cols[None, :] <= rows[:, None]
        if window is not None:
            mask &= cols[None, :] > rows[:, None] - window
        p = jnp.where(mask[None, None, None], jnp.exp(s - lse_g[..., None]), 0.0)
        dv = jnp.einsum("bkgts,bkgtd->bksd", p, gg)
        dp = jnp.einsum("bkgtd,bksd->bkgts", gg, v32)
        ds = p * (dp - delta_g[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bkgts,bksd->bkgtd", ds, k32)
        dk = jnp.einsum("bkgts,bkgtd->bksd", ds, qg)
        return dq_acc, (dk, dv)

    from ..models.layers import scan_layers as _scan  # unroll-aware
    dq0 = jnp.zeros((B, Hkv, group, T, Dh), jnp.float32)
    dq, (dks, dvs) = _scan(body, dq0, (jnp.arange(nblk), kb, vb))
    dq = dq.reshape(B, Hq, T, Dh).astype(q.dtype)
    dk = dks.transpose(1, 2, 0, 3, 4).reshape(B, Hkv, S, Dh).astype(k.dtype)
    dv = dvs.transpose(1, 2, 0, 3, 4).reshape(B, Hkv, S, Dh).astype(v.dtype)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q, k, v, *,
    causal: bool = True,
    window: Optional[int] = None,
    q_start: int = 0,
    block_q: int = 128,
    block_kv: int = 128,
):
    """Prefill attention; q (B,Hq,T,Dh), k/v (B,Hkv,S,Dh). Differentiable."""
    return _flash(q, k, v, causal, window, q_start, block_q, block_kv)


def decode_attention(q, k_cache, v_cache, lengths, *, block_kv: int = 512):
    """Single-token attention; q (B,Hq,Dh), caches (B,Hkv,S,Dh). No grad."""
    S = k_cache.shape[2]
    bs = min(block_kv, S)
    if _BACKEND == "reference" or S % bs != 0:
        return ref.decode_attention_ref(q, k_cache, v_cache, lengths)
    fn = functools.partial(_decode_kernel, block_kv=bs, interpret=_interpret())
    return _shard_mapped(
        fn,
        arg_axes=[("batch", "heads", None),
                  ("batch", "kv_heads", None, None),
                  ("batch", "kv_heads", None, None),
                  ("batch",)],
        out_axes=[("batch", "heads", None)],
        args=(q, k_cache, v_cache, lengths),
    )


# ---------------------------------------------------------------------------
# RG-LRU scan: adjoint = reversed linear recurrence (same kernel)
# ---------------------------------------------------------------------------


def _rglru_fwd_impl(a, u, h0, chunk, block_d):
    B, T, D = a.shape
    L, bd = min(chunk, T), min(block_d, D)
    if _BACKEND == "reference" or T % L != 0 or D % bd != 0:
        return ref.rglru_scan_ref(a, u, h0)
    fn = functools.partial(_rglru_kernel, chunk=L, block_d=bd,
                           interpret=_interpret())
    return _shard_mapped(
        fn,
        arg_axes=[("batch", None, "lru"), ("batch", None, "lru"),
                  ("batch", "lru")],
        out_axes=[("batch", None, "lru"), ("batch", "lru")],
        args=(a, u, h0),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rglru(a, u, h0, chunk, block_d):
    return _rglru_fwd_impl(a, u, h0, chunk, block_d)


def _rglru_vjp_fwd(a, u, h0, chunk, block_d):
    y, hT = _rglru_fwd_impl(a, u, h0, chunk, block_d)
    return (y, hT), (a, h0, y)


def _rglru_vjp_bwd(chunk, block_d, res, grads):
    a, h0, y = res
    gy, ghT = grads
    B, T, D = a.shape
    # total incoming gradient per step; the final-state grad lands on t=T-1
    g = gy.at[:, T - 1, :].add(ghT.astype(gy.dtype))
    # G_t = g_t + a_{t+1} G_{t+1}: run the same recurrence on flipped arrays
    a_next = jnp.concatenate([a[:, 1:, :], jnp.zeros_like(a[:, :1, :])], axis=1)
    G_rev, _ = _rglru_fwd_impl(
        jnp.flip(a_next, axis=1), jnp.flip(g, axis=1),
        jnp.zeros_like(h0), chunk, block_d)
    G = jnp.flip(G_rev, axis=1)
    du = G.astype(g.dtype)
    y_prev = jnp.concatenate([h0[:, None, :], y[:, :-1, :]], axis=1)
    da = (G.astype(jnp.float32) * y_prev.astype(jnp.float32)).astype(a.dtype)
    dh0 = (a[:, 0, :].astype(jnp.float32)
           * G[:, 0, :].astype(jnp.float32)).astype(h0.dtype)
    return da, du, dh0


_rglru.defvjp(_rglru_vjp_fwd, _rglru_vjp_bwd)


def rglru_scan(a, u, h0, *, chunk: int = 256, block_d: int = 256):
    """h_t = a_t h_{t-1} + u_t;  a, u (B,T,D); h0 (B,D). Differentiable."""
    return _rglru(a, u, h0, chunk, block_d)


# ---------------------------------------------------------------------------
# Mamba-2 SSD scan: backward via checkpointed chunked-jnp mirror
# ---------------------------------------------------------------------------


def _ssd_chunked_jnp(x, dt, A, Bm, Cm, h0, chunk):
    """Differentiable chunked SSD identical in math to the Pallas kernel."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    hg = H // G
    L = min(chunk, T)
    nc = T // L
    Bh = jnp.repeat(Bm, hg, axis=2).astype(jnp.float32)
    Ch = jnp.repeat(Cm, hg, axis=2).astype(jnp.float32)
    xc = x.astype(jnp.float32).reshape(B, nc, L, H, P).transpose(1, 0, 2, 3, 4)
    dtc = dt.astype(jnp.float32).reshape(B, nc, L, H).transpose(1, 0, 2, 3)
    Bc = Bh.reshape(B, nc, L, H, N).transpose(1, 0, 2, 3, 4)
    Cc = Ch.reshape(B, nc, L, H, N).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def body(h, inputs):
        xb, dtb, Bb, Cb = inputs  # (B,L,H,*)
        da = dtb * A[None, None, :]  # (B,L,H) <= 0
        cs = jnp.cumsum(da, axis=1)
        diff = cs[:, :, None, :] - cs[:, None, :, :]  # (B,L,L,H)
        causal = (jnp.arange(L)[:, None] >= jnp.arange(L)[None, :])[None, :, :, None]
        M = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
        Sm = jnp.einsum("blhn,bmhn->blmh", Cb, Bb) * M
        y_intra = jnp.einsum("blmh,bmhp->blhp", Sm, dtb[..., None] * xb)
        y_inter = jnp.exp(cs)[..., None] * jnp.einsum("blhn,bhnp->blhp", Cb, h)
        cs_L = cs[:, -1:, :]  # (B,1,H)
        w = Bb * (jnp.exp(cs_L - cs) * dtb)[..., None]  # (B,L,H,N)
        h_new = jnp.exp(cs_L)[:, 0, :, None, None] * h + \
            jnp.einsum("blhn,blhp->bhnp", w, xb)
        return h_new, y_intra + y_inter

    from ..models.layers import scan_layers as _scan  # unroll-aware
    hT, ys = _scan(body, h0.astype(jnp.float32), (xc, dtc, Bc, Cc))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, T, H, P)
    return y.astype(x.dtype), hT.astype(x.dtype)


def _ssd_fwd_impl(x, dt, A, Bm, Cm, h0, chunk):
    B, T, H, P = x.shape
    L = min(chunk, T)
    if _BACKEND == "reference" or T % L != 0:
        return ref.ssd_scan_ref(x, dt, A, Bm, Cm, h0)
    fn = functools.partial(_ssd_kernel, chunk=L, interpret=_interpret())
    return _shard_mapped(
        fn,
        arg_axes=[("batch", None, "inner_heads", None),
                  ("batch", None, "inner_heads"),
                  ("inner_heads",),
                  ("batch", None, "ssm_groups", None),
                  ("batch", None, "ssm_groups", None),
                  ("batch", "inner_heads", None, None)],
        out_axes=[("batch", None, "inner_heads", None),
                  ("batch", "inner_heads", None, None)],
        args=(x, dt, A, Bm, Cm, h0),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd(x, dt, A, Bm, Cm, h0, chunk):
    return _ssd_fwd_impl(x, dt, A, Bm, Cm, h0, chunk)


def _ssd_vjp_fwd(x, dt, A, Bm, Cm, h0, chunk):
    out = _ssd_fwd_impl(x, dt, A, Bm, Cm, h0, chunk)
    return out, (x, dt, A, Bm, Cm, h0)


def _ssd_vjp_bwd(chunk, res, grads):
    x, dt, A, Bm, Cm, h0 = res
    B, T, H, P = x.shape
    L = min(chunk, T)
    if T % L != 0:
        fn = lambda *args: ref.ssd_scan_ref(*args)
    else:
        fn = lambda *args: _ssd_chunked_jnp(*args, chunk)
    _, vjp = jax.vjp(fn, x, dt, A, Bm, Cm, h0)
    return vjp(grads)


_ssd.defvjp(_ssd_vjp_fwd, _ssd_vjp_bwd)


def ssd_scan(x, dt, A, Bm, Cm, h0, *, chunk: int = 128):
    """Mamba-2 SSD scan; x (B,T,H,P), dt (B,T,H), A (H,), Bm/Cm (B,T,G,N)."""
    return _ssd(x, dt, A, Bm, Cm, h0, chunk)
