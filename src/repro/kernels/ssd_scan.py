"""Pallas TPU kernel: Mamba-2 SSD (state-space duality) chunked scan.

Mamba-2's SSD form [arXiv:2405.21060] splits the sequence into chunks: the
intra-chunk contribution is a masked (L, L) matmul — MXU food — and the
inter-chunk contribution flows through a small (N, P) state carried between
chunks.  This maps perfectly onto a sequential Pallas grid axis with the
state in VMEM scratch:

  per chunk (head h, batch b):
    da      = dt * A_h                          (L,)    decay log-rates
    cs      = cumsum(da)                        (L,)    inclusive
    S       = C @ B^T  *  M                     (L, L)  M[i,j]=exp(cs_i-cs_j), j<=i
    y_intra = S @ (dt * x)                      (L, P)
    y_inter = exp(cs) * (C @ h_prev)            (L, P)
    h_next  = exp(cs_L) h_prev
              + (B * exp(cs_L - cs) * dt)^T @ x (N, P)

All exponents are <= 0 (A < 0, dt > 0) so everything is numerically tame.
Layout: x (B,T,H,P), dt (B,T,H), A (H,), Bm/Cm (B,T,G,N) with G groups
shared across H//G heads -> y (B,T,H,P), final state (B,H,N,P).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, h0_ref, y_ref, hT_ref,
                state, *, L):
    cidx = pl.program_id(2)

    @pl.when(cidx == 0)
    def _init():
        state[...] = h0_ref[0, 0].astype(jnp.float32)

    A = a_ref[pl.program_id(1)]  # scalar decay rate for this head
    x = x_ref[0, 0].astype(jnp.float32)  # (L, P)
    dt = dt_ref[0, 0].astype(jnp.float32)  # (L, 1)
    Bm = b_ref[0, 0].astype(jnp.float32)  # (L, N)
    Cm = c_ref[0, 0].astype(jnp.float32)  # (L, N)

    da = dt * A  # (L, 1), all <= 0
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = jj <= ii
    # inclusive cumsum as triangular matmuls (Mosaic lowers no cumsum):
    # cs_rows[i, j] = cs_i and cs_cols[i, j] = cs_j
    tril = causal.astype(jnp.float32)
    da_b = jnp.broadcast_to(da, (L, L))  # [i, j] = da_i
    hi = jax.lax.Precision.HIGHEST
    cs_rows = jax.lax.dot(tril, da_b, precision=hi,
                          preferred_element_type=jnp.float32)
    cs_cols = jax.lax.dot_general(
        da_b, tril, (((0,), (1,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)
    cs = cs_rows[:, :1]  # (L, 1)
    # intra-chunk: masked decay matrix
    diff = cs_rows - cs_cols  # (L, L): cs_i - cs_j
    M = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
    S = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * M  # (L, L)
    y_intra = jax.lax.dot(S, dt * x, preferred_element_type=jnp.float32)
    # inter-chunk via carried state
    h_prev = state[...]  # (N, P)
    y_inter = jnp.exp(cs) * jax.lax.dot(
        Cm, h_prev, preferred_element_type=jnp.float32
    )  # (L, P)
    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)
    # state update
    cs_L = cs[-1:, :]  # (1, 1)
    w = Bm * jnp.exp(cs_L - cs) * dt  # (L, N)
    # the chunk's total decay, filled over (N, P) by a ones matmul: Mosaic
    # cannot broadcast a (1, 1) value over sublanes and lanes at once
    N, P = h_prev.shape
    total = jax.lax.dot(jnp.ones((N, L), jnp.float32),
                        jnp.broadcast_to(da, (L, P)), precision=hi,
                        preferred_element_type=jnp.float32)
    state[...] = jnp.exp(total) * h_prev + jax.lax.dot_general(
        w, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(cidx == pl.num_programs(2) - 1)
    def _final():
        hT_ref[0, 0] = state[...].astype(hT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x, dt, A, Bm, Cm, h0, *,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = True,
):
    """Mamba-2 SSD scan.

    x (B,T,H,P); dt (B,T,H); A (H,); Bm, Cm (B,T,G,N); h0 (B,H,N,P).
    Returns y (B,T,H,P), hT (B,H,N,P).
    """
    B, T, H, P = x.shape
    _, _, G, N = Bm.shape
    assert H % G == 0
    hg = H // G
    L = min(chunk, T)
    assert T % L == 0
    grid = (B, H, T // L)
    kernel = functools.partial(_ssd_kernel, L=L)
    # head-major layouts, so every block's last two dims are (L, full):
    # x (B,H,T,P), dt (B,H,T,1), Bm/Cm (B,G,T,N)
    xh = x.transpose(0, 2, 1, 3)
    dth = dt.transpose(0, 2, 1)[..., None]
    Bh = Bm.transpose(0, 2, 1, 3)
    Ch = Cm.transpose(0, 2, 1, 3)
    y, hT = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # the whole (H,) rate vector in SMEM; the kernel indexes its head
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, L, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, h, c, g=hg: (b, h // g, c, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, h, c, g=hg: (b, h // g, c, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(A.astype(jnp.float32), xh, dth, Bh, Ch, h0)
    return y.transpose(0, 2, 1, 3), hT
