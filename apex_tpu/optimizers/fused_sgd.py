"""Fused SGD with momentum.

Re-design of ``apex.optimizers.FusedSGD`` (``apex/optimizers/fused_sgd.py:6``;
kernel ``csrc/multi_tensor_sgd_kernel.cu``): classic torch-SGD semantics —
L2 weight decay into the gradient, momentum buffer
``buf = momentum*buf + (1-dampening)*g``, optional Nesterov
(``g + momentum*buf``), ``first_run`` initializing the buffer to the gradient.

The reference's special amp integration (unscale folded into the step so fp16
master grads never materialize, ``fused_sgd.py:79,95,175``) is expressed here
by the optional ``grad_scale`` argument of the kernel: pass the loss-scale
reciprocal and the unscale fuses into the same pass.
"""

from __future__ import annotations

import jax.numpy as jnp
import optax

from apex_tpu.optimizers import multi_tensor as mt
from apex_tpu.optimizers._fused import (
    make_fused_transform, make_per_tensor_transform, resolve_layout,
    schedule_value)


def fused_sgd(
    learning_rate=1e-3,
    momentum: float = 0.0,
    dampening: float = 0.0,
    weight_decay: float = 0.0,
    nesterov: bool = False,
    grad_scale: float = 1.0,
    chunk_size: int = None,  # explicit value implies layout='chunked'
    layout: str = "auto",
) -> optax.GradientTransformation:
    if nesterov and (momentum <= 0 or dampening != 0):
        raise ValueError("nesterov requires momentum > 0 and zero dampening")

    def kernel(g, p, buffers, scalars, count, layout):
        if grad_scale != 1.0:
            g = g * (1.0 / grad_scale)  # fused unscale (fused_sgd.py:212)
        if weight_decay:
            g = g + weight_decay * p
        if momentum:
            buf = buffers["momentum"]
            first = count == 1
            buf = jnp.where(first, g, momentum * buf + (1.0 - dampening) * g)
            d_p = g + momentum * buf if nesterov else buf
            new_buffers = {"momentum": buf}
        else:
            d_p = g
            new_buffers = buffers
        lr = schedule_value(learning_rate, count)
        return p - lr * d_p, new_buffers, scalars

    if resolve_layout(layout, chunk_size) == "per_tensor":
        # the kernel is purely elementwise — reuse it per leaf
        return make_per_tensor_transform(
            name="fused_sgd", state_buffers=("momentum",) if momentum else (),
            leaf_kernel=lambda g, p, b, sc, c, stats: kernel(g, p, b, sc, c, None),
        )

    return make_fused_transform(
        name="fused_sgd", state_buffers=("momentum",) if momentum else (),
        kernel=kernel,
        chunk_size=chunk_size or mt.DEFAULT_CHUNK,
    )


FusedSGD = fused_sgd
