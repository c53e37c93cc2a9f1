"""Fused NovoGrad.

Re-design of ``apex.optimizers.FusedNovoGrad``
(``apex/optimizers/fused_novograd.py``; kernel
``csrc/multi_tensor_novograd.cu:100-140``). The second moment is a *per-tensor
scalar* norm of the gradient, not an elementwise buffer:

* the state stores the *norm itself*, not its square ("we store norm here
  (not ^2) so we can unify calculation for norm types",
  ``fused_novograd.py:160-162``): ``v_t = b2*v + (1-b2)*||g||`` and
  ``denom = v_t / sqrt(1-b2^t) + eps`` (``novograd.cu:151,99``)
* ``norm_type=2``: L2 norm; ``norm_type=0``: infinity norm via segment-max
* ``init_zero=False`` (default): first step initializes ``v`` to the first
  norm instead of averaging from zero (``fused_novograd.py:55-58``)
* ``reg_inside_moment`` selects where weight decay / normalization enter
  (moment_mode 0 vs 1, ``novograd.cu:100-112``)
* ``grad_averaging``: ``beta3 = 1-b1`` applied to the (normalized) grad

Per-tensor norms come from the chunked layout's segment reduction; the scalar
``v`` vector lives in ``state.scalars`` — tiny, exactly like the reference's
per-tensor ``grad_norms`` tensor.
"""

from __future__ import annotations

import jax.numpy as jnp
import optax

from apex_tpu.optimizers import multi_tensor as mt
from apex_tpu.optimizers._fused import (
    make_fused_transform, make_per_tensor_transform, resolve_layout,
    schedule_value)


def fused_novograd(
    learning_rate=1e-3,
    b1: float = 0.95,
    b2: float = 0.98,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    grad_averaging: bool = True,
    reg_inside_moment: bool = False,
    norm_type: int = 2,
    init_zero: bool = False,
    bias_correction: bool = False,
    chunk_size: int = None,  # explicit value implies layout='chunked'
    layout: str = "auto",
) -> optax.GradientTransformation:
    if norm_type not in (0, 2):
        raise ValueError("norm_type must be 2 (L2) or 0 (inf)")

    def _common(g, p, m, v, gnorm, count, broadcast):
        step = count.astype(jnp.float32)
        beta3 = 1.0 - b1 if grad_averaging else 1.0
        first = count == 1
        if init_zero:
            v_new = b2 * v + (1.0 - b2) * gnorm
        else:
            v_new = jnp.where(first, gnorm, b2 * v + (1.0 - b2) * gnorm)
        if bias_correction:
            v_unbiased = v_new / jnp.sqrt(1.0 - b2 ** step)
            b1_corr = 1.0 - b1 ** step
        else:
            v_unbiased = v_new
            b1_corr = 1.0
        denom = broadcast(v_unbiased + eps)
        if reg_inside_moment:  # moment_mode 0 (novograd.cu:100-105)
            g_term = g / denom + weight_decay * p
            m = b1 * m + beta3 * g_term
            update = m / b1_corr
        else:  # moment_mode 1 (novograd.cu:107-112)
            m = b1 * m + beta3 * g
            update = (m / b1_corr) / denom + weight_decay * p
        lr = schedule_value(learning_rate, count)
        return p - lr * update, m, v_new

    if resolve_layout(layout, chunk_size) == "per_tensor":
        def leaf_kernel(g, p, bufs, scal, count, stats):
            gnorm = (jnp.sqrt(jnp.sum(g * g)) if norm_type == 2
                     else jnp.max(jnp.abs(g)))
            new_p, m, v_new = _common(
                g, p, bufs["m"], scal["v"], gnorm, count, lambda s: s)
            return new_p, {"m": m}, {"v": v_new}

        return make_per_tensor_transform(
            name="fused_novograd", state_buffers=("m",), state_scalars=("v",),
            leaf_kernel=leaf_kernel)

    def kernel(g, p, buffers, scalars, count, layout):
        # the NORM is blended, not its square (reference
        # fused_novograd.py:160-177); beta2_correction = sqrt(1-b2^t)
        # (novograd.cu:151)
        if norm_type == 2:
            gnorm = jnp.sqrt(mt.per_tensor_sqnorm(g, layout))
        else:
            gnorm = mt.per_tensor_maxnorm(g, layout)
        new_p, m, v_new = _common(
            g, p, buffers["m"], scalars["v"], gnorm, count,
            lambda s: mt.broadcast_per_tensor(s, layout))
        return new_p, {"m": m}, {"v": v_new}

    return make_fused_transform(
        name="fused_novograd", state_buffers=("m",), state_scalars=("v",), kernel=kernel, chunk_size=chunk_size or mt.DEFAULT_CHUNK
    )


FusedNovoGrad = fused_novograd
