"""Fused LAMB.

Re-design of ``apex.optimizers.FusedLAMB`` (``apex/optimizers/fused_lamb.py:4``;
kernels ``csrc/multi_tensor_lamb.cu``). Two-phase algorithm preserved:

1. global grad norm over ALL params (the reference blends fp16+fp32 lists,
   ``fused_lamb.py:120-141``); grads divided by
   ``clipped = max(global_norm / max_grad_norm, 1)`` (``multi_tensor_lamb.cu:66``)
2. Adam-style moments on the clipped grad; update term
   ``m_hat/(sqrt(v_hat)+eps) + wd*p``; per-tensor trust ratio
   ``ratio = lr * ||p|| / ||update||`` applied when ``use_nvlamb`` or
   ``wd != 0`` and both norms are nonzero (``multi_tensor_lamb.cu:255-262``)

Phase 1's per-tensor norms ride the chunked layout's segment reduction — the
whole optimizer is two fused passes + two tiny segment ops, matching the
reference's two multi-tensor launches.
"""

from __future__ import annotations

import jax.numpy as jnp
import optax

import jax

from apex_tpu.optimizers import multi_tensor as mt
from apex_tpu.optimizers._fused import (
    make_fused_transform, make_per_tensor_transform, resolve_layout,
    schedule_value)


def lamb_update_math(
    g, p, m, v, count, clipped, *, sqnorm, broadcast,
    learning_rate, b1, b2, eps, weight_decay, bias_correction,
    grad_averaging, use_nvlamb,
):
    """Phase-2 LAMB math, layout-injected: ``sqnorm(t)`` returns per-tensor
    squared norms and ``broadcast(r)`` expands per-tensor scalars back to
    ``t``'s shape — identity/scalar for the per-tensor layout, segment ops
    for the chunked buffer. One copy of the formula serves both layouts and
    ``fused_mixed_precision_lamb``. Returns ``(new_p, new_m, new_v)``."""
    step = count.astype(jnp.float32)
    beta3 = 1.0 - b1 if grad_averaging else 1.0
    g = g / clipped

    m = b1 * m + beta3 * g
    v = b2 * v + (1.0 - b2) * g * g
    if bias_correction:
        m_hat = m / (1.0 - b1 ** step)
        v_hat = v / (1.0 - b2 ** step)
    else:
        m_hat, v_hat = m, v
    update = m_hat / (jnp.sqrt(v_hat) + eps)
    if weight_decay:
        update = update + weight_decay * p

    # per-tensor trust ratios (lamb.cu:244-262)
    p_norm = jnp.sqrt(sqnorm(p))
    u_norm = jnp.sqrt(sqnorm(update))
    lr = schedule_value(learning_rate, count)
    if use_nvlamb or weight_decay != 0.0:
        ratio = jnp.where((p_norm > 0.0) & (u_norm > 0.0),
                          lr * p_norm / u_norm,
                          jnp.full_like(p_norm, lr))
    else:
        ratio = jnp.full_like(p_norm, lr)
    return p - broadcast(ratio) * update, m, v


def clip_by_global_norm(gnorm, max_grad_norm):
    """phase 1's divisor (fused_lamb.py:120-141, lamb.cu:66)."""
    return jnp.where(gnorm > max_grad_norm, gnorm / max_grad_norm, 1.0)


def lamb_chunked_update(
    g, p, m, v, count, layout, *,
    learning_rate, b1, b2, eps, weight_decay, bias_correction,
    grad_averaging, max_grad_norm, use_nvlamb,
):
    """The two-phase LAMB math over chunked buffers; shared by
    :func:`fused_lamb` and ``fused_mixed_precision_lamb``.

    Returns ``(new_p, new_m, new_v)``.
    """
    clipped = clip_by_global_norm(mt.global_norm(g), max_grad_norm)
    return lamb_update_math(
        g, p, m, v, count, clipped,
        sqnorm=lambda t: mt.per_tensor_sqnorm(t, layout),
        broadcast=lambda r: mt.broadcast_per_tensor(r, layout),
        learning_rate=learning_rate, b1=b1, b2=b2, eps=eps,
        weight_decay=weight_decay, bias_correction=bias_correction,
        grad_averaging=grad_averaging, use_nvlamb=use_nvlamb,
    )


def fused_lamb(
    learning_rate=1e-3,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-6,
    weight_decay: float = 0.01,
    bias_correction: bool = True,
    grad_averaging: bool = True,
    adam_w_mode: bool = True,
    max_grad_norm: float = 1.0,
    use_nvlamb: bool = False,
    chunk_size: int = None,  # explicit value implies layout='chunked'
    layout: str = "auto",
) -> optax.GradientTransformation:
    if resolve_layout(layout, chunk_size) == "per_tensor":
        def global_stats(g32, count):
            # phase 1: global norm over ALL params (fused_lamb.py:120-141)
            gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g32)))
            return clip_by_global_norm(gnorm, max_grad_norm)

        def leaf_kernel(g, p, bufs, scal, count, clipped):
            new_p, m, v = lamb_update_math(
                g, p, bufs["m"], bufs["v"], count, clipped,
                sqnorm=lambda t: jnp.sum(t * t),
                broadcast=lambda r: r,
                learning_rate=learning_rate, b1=b1, b2=b2, eps=eps,
                weight_decay=weight_decay, bias_correction=bias_correction,
                grad_averaging=grad_averaging, use_nvlamb=use_nvlamb,
            )
            return new_p, {"m": m, "v": v}, scal

        return make_per_tensor_transform(
            name="fused_lamb", state_buffers=("m", "v"), leaf_kernel=leaf_kernel,
            global_stats=global_stats)

    def kernel(g, p, buffers, scalars, count, layout_):
        new_p, m, v = lamb_chunked_update(
            g, p, buffers["m"], buffers["v"], count, layout_,
            learning_rate=learning_rate, b1=b1, b2=b2, eps=eps,
            weight_decay=weight_decay, bias_correction=bias_correction,
            grad_averaging=grad_averaging, max_grad_norm=max_grad_norm,
            use_nvlamb=use_nvlamb,
        )
        return new_p, {"m": m, "v": v}, scalars

    return make_fused_transform(
        name="fused_lamb", state_buffers=("m", "v"), kernel=kernel, chunk_size=chunk_size or mt.DEFAULT_CHUNK
    )


FusedLAMB = fused_lamb
