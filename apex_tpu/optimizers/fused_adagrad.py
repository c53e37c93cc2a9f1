"""Fused Adagrad.

Re-design of ``apex.optimizers.FusedAdagrad``
(``apex/optimizers/fused_adagrad.py``; kernel
``csrc/multi_tensor_adagrad.cu``): ``h += g^2``,
``p -= lr * g / (sqrt(h) + eps)``, with "adagrad_w"-style decoupled weight
decay when ``adagrad_w_mode`` (the reference's ``adagrad_w_mode`` adds
``wd*p`` to the update; plain mode folds L2 into the gradient).
"""

from __future__ import annotations

import jax.numpy as jnp
import optax

from apex_tpu.optimizers import multi_tensor as mt
from apex_tpu.optimizers._fused import (
    make_fused_transform, make_per_tensor_transform, resolve_layout,
    schedule_value)


def fused_adagrad(
    learning_rate=1e-2,
    eps: float = 1e-10,
    weight_decay: float = 0.0,
    adagrad_w_mode: bool = False,
    chunk_size: int = None,  # explicit value implies layout='chunked'
    layout: str = "auto",
) -> optax.GradientTransformation:
    def kernel(g, p, buffers, scalars, count, layout):
        h = buffers["h"]
        if not adagrad_w_mode and weight_decay:
            g = g + weight_decay * p
        h = h + g * g
        update = g / (jnp.sqrt(h) + eps)
        if adagrad_w_mode and weight_decay:
            update = update + weight_decay * p
        lr = schedule_value(learning_rate, count)
        return p - lr * update, {"h": h}, scalars

    if resolve_layout(layout, chunk_size) == "per_tensor":
        return make_per_tensor_transform(
            name="fused_adagrad", state_buffers=("h",),
            leaf_kernel=lambda g, p, b, sc, c, stats: kernel(g, p, b, sc, c, None),
        )

    return make_fused_transform(name="fused_adagrad", state_buffers=("h",), kernel=kernel, chunk_size=chunk_size or mt.DEFAULT_CHUNK)


FusedAdagrad = fused_adagrad
