"""Pallas TPU kernel: tiled (flash) attention for prefill.

Supports causal masking, grouped-query attention (Hkv <= Hq), and sliding
windows (RecurrentGemma local attention).  Streaming-softmax accumulation
runs in VMEM scratch across a sequential KV-block grid axis; fully-masked
KV blocks are skipped via ``pl.when``, which on TPU elides both the compute
and the HBM->VMEM copies for ~2x on causal prefill.

Layout: q (B, Hq, T, Dh), k/v (B, Hkv, S, Dh) -> out (B, Hq, T, Dh).
Block sizes default to 128x128 (MXU-aligned); Dh must be a multiple of 128
on real TPUs — interpret mode (CPU validation) accepts anything.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BT = 128
DEFAULT_BS = 128
NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
    *, scale, causal, window, q_start, bt, bs,
):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    row0 = q_start + i * bt  # absolute query positions
    col0 = j * bs
    visible = jnp.bool_(True)
    if causal:
        visible &= col0 <= row0 + bt - 1
    if window is not None:
        visible &= col0 + bs - 1 >= row0 - window + 1

    @pl.when(visible)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bt, dh)
        k = k_ref[0, 0].astype(jnp.float32)  # (bs, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bt, bs)
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bt, bs), 0)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (bt, bs), 1)
        mask = jnp.ones((bt, bs), dtype=jnp.bool_)
        if causal:
            mask &= cols <= rows
        if window is not None:
            mask &= cols > rows - window
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

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)
        # log-sum-exp per query row (saved for the flash backward pass)
        lse = m_ref[...] + jnp.log(safe)
        lse_ref[0, 0] = jnp.where(l == 0.0, NEG_INF, lse)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_start", "block_q", "block_kv",
                     "interpret"),
)
def flash_attention(
    q, k, v, *,
    causal: bool = True,
    window: int | None = None,
    q_start: int = 0,
    block_q: int = DEFAULT_BT,
    block_kv: int = DEFAULT_BS,
    interpret: bool = True,
):
    """Tiled attention.  q (B,Hq,T,Dh); k,v (B,Hkv,S,Dh) -> (B,Hq,T,Dh)
    and the per-row log-sum-exp (B,Hq,T)."""
    B, Hq, T, Dh = q.shape
    _, Hkv, S, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    bt, bs = min(block_q, T), min(block_kv, S)
    assert T % bt == 0 and S % bs == 0, (T, bt, S, bs)
    grid = (B, Hq, T // bt, S // bs)
    scale = 1.0 / (Dh ** 0.5)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        q_start=q_start, bt=bt, bs=bs,
    )
    # lse travels as (B, Hq, T, 1): a (bt, 1) block is tile-aligned on the
    # chip, where a (1, bt) slice of a (B, Hq, T) array is not
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bt, Dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bs, Dh), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bs, Dh), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bt, Dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bt, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, T, Dh), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, T, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, Dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse[..., 0]
