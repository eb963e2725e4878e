"""Pallas TPU kernel: batched last-writer-wins lattice merge (paper §5.2).

Anna merges values on every write and on every replica-gossip exchange; for
tensor-valued state (parameter shards, KV pages, metric vectors) this is the
storage layer's compute hot-spot.  On AWS the merge was a per-key C++
branch; the TPU-native rethink is to *batch* K keys of D payload elements
into one kernel launch so the HBM->VMEM streams stay saturated and the
select runs on the 8x128 VPU lanes.

Timestamps are Lamport pairs ``(clock, node_rank)`` (int32 each), compared
lexicographically — identical to ``lattices.LWWLattice.merge``.

Two entry points:
* ``lww_merge``: merge two replica batches (A vs B);
* ``lww_merge_many``: reduce R replica batches (the gossip-repair path),
  streaming replicas through VMEM with a running (ts, value) accumulator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Block sizes: rows of keys x payload lanes.  8x128 is the VPU tile; we use
# multiples so the MXU/VPU stay aligned and a block (2 payloads + masks)
# stays well under VMEM (~16 MB): 2 * BK*BD * 4B = 512 KB.
BK = 8
BD = 512


def _block_d(D: int) -> int:
    """Payload lanes per block: the widest of 512/256/128 that divides D
    (so every D % 128 == 0 payload tiles), else the full width."""
    for bd in (BD, 256, 128):
        if D % bd == 0:
            return bd
    return D


def _pred_newer(clock_a, node_a, clock_b, node_b):
    """Lexicographic (clock, node) >= — matches LWWLattice.merge ties."""
    return (clock_a > clock_b) | ((clock_a == clock_b) & (node_a >= node_b))


def _merge_kernel(clock_a_ref, node_a_ref, val_a_ref, clock_b_ref,
                  node_b_ref, val_b_ref, val_o_ref, clock_o_ref, node_o_ref):
    pred = _pred_newer(
        clock_a_ref[...], node_a_ref[...], clock_b_ref[...], node_b_ref[...]
    )  # (BK, 1) bool
    val_o_ref[...] = jnp.where(pred, val_a_ref[...], val_b_ref[...])
    clock_o_ref[...] = jnp.where(pred, clock_a_ref[...], clock_b_ref[...])
    node_o_ref[...] = jnp.where(pred, node_a_ref[...], node_b_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def lww_merge(clock_a, node_a, val_a, clock_b, node_b, val_b, *, interpret=True):
    """Merge two batches of LWW registers.

    Args:
      clock_*/node_*: (K, 1) int32 Lamport components.
      val_*: (K, D) payloads (any dtype).
    Returns:
      (val, clock, node) of the winning registers.
    """
    K, D = val_a.shape
    bk, bd = min(BK, K), _block_d(D)
    assert K % bk == 0, (K, D)
    grid = (K // bk, D // bd)
    ts_spec = pl.BlockSpec((bk, 1), lambda i, j: (i, 0))
    val_spec = pl.BlockSpec((bk, bd), lambda i, j: (i, j))
    return pl.pallas_call(
        _merge_kernel,
        grid=grid,
        in_specs=[ts_spec, ts_spec, val_spec, ts_spec, ts_spec, val_spec],
        out_specs=[val_spec, ts_spec, ts_spec],
        out_shape=[
            jax.ShapeDtypeStruct((K, D), val_a.dtype),
            jax.ShapeDtypeStruct((K, 1), jnp.int32),
            jax.ShapeDtypeStruct((K, 1), jnp.int32),
        ],
        interpret=interpret,
    )(clock_a, node_a, val_a, clock_b, node_b, val_b)


def _merge_many_kernel(clock_ref, node_ref, val_ref, val_o_ref, clock_o_ref,
                       node_o_ref, acc_val, acc_clock, acc_node):
    r = pl.program_id(2)

    @pl.when(r == 0)
    def _():
        acc_val[...] = val_ref[0]
        acc_clock[...] = clock_ref[0]
        acc_node[...] = node_ref[0]

    @pl.when(r > 0)
    def _():
        pred = _pred_newer(
            acc_clock[...], acc_node[...], clock_ref[0], node_ref[0]
        )
        acc_val[...] = jnp.where(pred, acc_val[...], val_ref[0])
        acc_clock[...] = jnp.where(pred, acc_clock[...], clock_ref[0])
        acc_node[...] = jnp.where(pred, acc_node[...], node_ref[0])

    @pl.when(r == pl.num_programs(2) - 1)
    def _():
        val_o_ref[...] = acc_val[...]
        clock_o_ref[...] = acc_clock[...]
        node_o_ref[...] = acc_node[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def lww_merge_many(clocks, nodes, vals, *, interpret=True):
    """Reduce R replica batches: clocks/nodes (R, K, 1), vals (R, K, D)."""
    R, K, D = vals.shape
    bk, bd = min(BK, K), _block_d(D)
    assert K % bk == 0, (K, D)
    # replica axis innermost => sequential with carried scratch accumulator
    grid = (K // bk, D // bd, R)
    ts_spec = pl.BlockSpec((1, bk, 1), lambda i, j, r: (r, i, 0))
    val_spec = pl.BlockSpec((1, bk, bd), lambda i, j, r: (r, i, j))
    ts_out = pl.BlockSpec((bk, 1), lambda i, j, r: (i, 0))
    val_out = pl.BlockSpec((bk, bd), lambda i, j, r: (i, j))
    return pl.pallas_call(
        _merge_many_kernel,
        grid=grid,
        in_specs=[ts_spec, ts_spec, val_spec],
        out_specs=[val_out, ts_out, ts_out],
        out_shape=[
            jax.ShapeDtypeStruct((K, D), vals.dtype),
            jax.ShapeDtypeStruct((K, 1), jnp.int32),
            jax.ShapeDtypeStruct((K, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, bd), vals.dtype),
            pltpu.VMEM((bk, 1), jnp.int32),
            pltpu.VMEM((bk, 1), jnp.int32),
        ],
        interpret=interpret,
    )(clocks, nodes, vals)
