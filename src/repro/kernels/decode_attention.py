"""Pallas TPU kernel: single-token decode attention against a large KV cache.

Decode is memory-bound: one query token per sequence must stream the whole
(S, Dh) KV cache from HBM.  The TPU-native layout trick: put the *query
heads of one KV group* in the sublane (row) dimension, so GQA groups share
each streamed KV block and rows of the 8x128 tile are not wasted — e.g.
llama3.2 (24 q heads, 8 kv heads) gives 3 rows per group; we pad groups to
8 rows so one tile covers the group.

Layout: q (B, Hq, Dh), cache k/v (B, Hkv, S, Dh), lengths (B,) valid-length
mask -> out (B, Hq, Dh).  Grid (B, Hkv, S//BS) with the KV-block axis
sequential (streaming-softmax scratch carry); ``lengths`` rides scalar
prefetch into SMEM, so each grid step reads its row's length directly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BS = 512
NEG_INF = -1e30


def _decode_kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale, bs, group,
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[pl.program_id(0)]
    col0 = j * bs

    @pl.when(col0 < length)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (group, dh)
        k = k_ref[0, 0].astype(jnp.float32)  # (bs, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (group, bs)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (group, bs), 1)
        mask = cols < length
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.where(m_prev <= NEG_INF, 0.0, jnp.exp(m_prev - m_new))
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_kv", "interpret")
)
def decode_attention(
    q, k_cache, v_cache, lengths, *,
    block_kv: int = DEFAULT_BS,
    interpret: bool = True,
):
    """One-token attention. q (B,Hq,Dh); caches (B,Hkv,S,Dh); lengths (B,)."""
    B, Hq, Dh = q.shape
    _, Hkv, S, _ = k_cache.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    bs = min(block_kv, S)
    assert S % bs == 0
    # regroup queries: (B, Hkv, group, Dh) so each kv head sees its q rows
    qg = q.reshape(B, Hkv, group, Dh)
    grid = (B, Hkv, S // bs)
    kernel = functools.partial(_decode_kernel, scale=1.0 / (Dh ** 0.5),
                               bs=bs, group=group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, group, Dh), lambda b, h, j, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, Dh), lambda b, h, j, lens: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bs, Dh), lambda b, h, j, lens: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, Dh),
                               lambda b, h, j, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, Dh), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k_cache, v_cache)
    return out.reshape(B, Hq, Dh)
