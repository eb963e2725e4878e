"""Train step factory: grad accumulation, remat, optional int8 cross-pod
gradient compression with error feedback (beyond-paper optimization).

The compression targets the slow link: on a multi-pod mesh the gradient
all-reduce crosses DCN on the 'pod' axis.  With ``grad_compression=True``
the step computes per-pod gradients (shard_map manual over 'pod', auto over
the in-pod axes), quantizes them to int8 with a per-tensor scale plus an
error-feedback accumulator, psums the int8 payload over 'pod', and
dequantizes — 4x less DCN traffic at equal asymptotic convergence
(error feedback makes the quantization unbiased over time).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models.model import Model
from . import optimizer as opt


def make_loss_fn(model: Model, remat: str):
    def loss_fn(params, batch):
        return model.loss(params, batch, remat=remat)
    return loss_fn


def grads_with_accumulation(loss_fn, params, batch, microbatches: int,
                            grad_shardings=None):
    """Split the batch into microbatches; accumulate fp32 grads via scan.

    ``grad_shardings`` pins each microbatch's gradients to the ZeRO layout
    *inside* the scan body — without it XLA reshards the per-microbatch
    grads to the accumulator layout via all-gather-then-slice (full-size
    fp32 expert tensors on every chip, the dominant wire on arctic).
    """
    def pin(grads):
        if grad_shardings is None:
            return grads
        return jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(g, s),
            grads, grad_shardings)

    if microbatches <= 1:
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        return loss, pin(grads)

    from ..pshard import constrain

    def reshape(x):
        b = x.shape[0]
        assert b % microbatches == 0, (b, microbatches)
        out = x.reshape(microbatches, b // microbatches, *x.shape[1:])
        # re-pin the batch sharding on the new dim-1 (the reshape would
        # otherwise let SPMD replicate every microbatch on every chip)
        return constrain(out, None, "batch", *([None] * (out.ndim - 2)))

    mb = jax.tree.map(reshape, batch)

    def body(acc, microbatch):
        loss_acc, grads_acc = acc
        loss, grads = jax.value_and_grad(loss_fn)(params, microbatch)
        grads = pin(grads)
        grads_acc = jax.tree.map(
            lambda a, g: a + g.astype(jnp.float32), grads_acc, grads)
        return (loss_acc + loss, pin(grads_acc)), None

    # derive the accumulator from params so it INHERITS their sharding
    # (a bare zeros() is unsharded and forces full-size gradient gathers)
    zeros = jax.tree.map(lambda p: (p * 0).astype(jnp.float32), params)
    zeros = pin(zeros)
    (loss, grads), _ = jax.lax.scan(body, (jnp.zeros(()), zeros), mb)
    inv = 1.0 / microbatches
    return loss * inv, jax.tree.map(lambda g: g * inv, grads)


# ---------------------------------------------------------------------------
# int8 cross-pod gradient compression with error feedback
# ---------------------------------------------------------------------------


def quantize_psum_pod(g: jax.Array, err: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Inside shard_map(manual over 'pod'): compress-reduce one tensor."""
    g32 = g.astype(jnp.float32) + err
    scale = jnp.maximum(jnp.max(jnp.abs(g32)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
    err_new = g32 - q.astype(jnp.float32) * scale
    q_sum = jax.lax.psum(q.astype(jnp.int32), "pod")
    scale_max = jax.lax.pmax(scale, "pod")  # conservative shared scale
    n = jax.lax.psum(jnp.ones(()), "pod")
    return (q_sum.astype(jnp.float32) * scale_max / n).astype(g.dtype), err_new


def make_train_step(
    model: Model,
    opt_cfg: opt.AdamWConfig,
    *,
    remat: str = "none",
    microbatches: int = 1,
    grad_compression: bool = False,
    mesh=None,
    grad_shardings=None,
) -> Callable:
    """Returns step(params, opt_state, batch[, err_fb]) -> (params, state, metrics).

    ``grad_shardings`` (a pytree of NamedShardings matching the ZeRO-1
    optimizer-state layout) pins the gradients to the sharded layout
    *before* the optimizer — XLA then reduces them with reduce-scatters
    instead of materializing full-size fp32 gradients on every chip
    (ZeRO-2 semantics; on arctic-480b this is the difference between a
    35 GB all-reduce and a 0.14 GB reduce-scatter per expert tensor).
    """
    loss_fn = make_loss_fn(model, remat)

    def pin_grads(grads):
        if grad_shardings is None:
            return grads
        return jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(g, s),
            grads, grad_shardings)

    if not grad_compression:
        def step(params, opt_state, batch):
            loss, grads = grads_with_accumulation(loss_fn, params, batch,
                                                  microbatches,
                                                  grad_shardings)
            grads = pin_grads(grads)
            params, opt_state = opt.apply_updates(opt_cfg, params, grads, opt_state)
            metrics = {"loss": loss, "grad_norm": opt.global_norm(grads),
                       "lr": opt.lr_at(opt_cfg, opt_state["step"])}
            return params, opt_state, metrics
        return step

    assert mesh is not None and "pod" in mesh.shape, \
        "grad compression reduces over the 'pod' axis"

    def per_pod_grads(params, batch):
        loss, grads = grads_with_accumulation(loss_fn, params, batch,
                                              microbatches)
        return loss, grads

    def step(params, opt_state, batch, err_fb):
        def inner(params, batch, err_fb):
            loss, grads = per_pod_grads(params, batch)
            out = jax.tree.map(quantize_psum_pod, grads, err_fb)
            grads_c = jax.tree.map(lambda t: t[0], out,
                                   is_leaf=lambda t: isinstance(t, tuple))
            err_new = jax.tree.map(lambda t: t[1], out,
                                   is_leaf=lambda t: isinstance(t, tuple))
            loss = jax.lax.pmean(loss, "pod")
            return loss, grads_c, err_new

        # manual over 'pod' (so we control the DCN reduction), auto elsewhere
        pspec = jax.tree.map(lambda _: P(), params)
        bspec = jax.tree.map(lambda _: P("pod"), batch)
        espec = jax.tree.map(lambda _: P(), err_fb)
        loss, grads, err_new = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(pspec, bspec, espec),
            out_specs=(P(), pspec, espec),
            axis_names=frozenset({"pod"}),
            check_vma=False,
        )(params, batch, err_fb)
        params, opt_state = opt.apply_updates(opt_cfg, params, grads, opt_state)
        metrics = {"loss": loss, "grad_norm": opt.global_norm(grads),
                   "lr": opt.lr_at(opt_cfg, opt_state["step"])}
        return params, opt_state, metrics, err_new

    return step


def init_error_feedback(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
