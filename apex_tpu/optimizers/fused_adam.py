"""Fused Adam / AdamW.

Re-design of ``apex.optimizers.FusedAdam`` (``apex/optimizers/fused_adam.py:4``;
kernel ``csrc/multi_tensor_adam.cu:25-140``). Semantics preserved:

* ``adam_w_mode=True`` (default): decoupled weight decay
  (``ADAM_MODE_1``) — ``p -= lr * (m_hat/(sqrt(v_hat)+eps) + wd*p)``
* ``adam_w_mode=False``: L2 mode (``ADAM_MODE_0``) — ``g += wd*p`` before the
  moment updates
* ``bias_correction`` on by default
* all math fp32; one fused pass over the whole parameter set

The whole update is one XLA loop over the chunked mega-buffer — the TPU
equivalent of the single ``multi_tensor_adam`` launch.
"""

from __future__ import annotations

import jax.numpy as jnp
import optax

from apex_tpu.optimizers import multi_tensor as mt
from apex_tpu.optimizers._fused import (
    make_fused_transform, make_per_tensor_transform, resolve_layout,
    schedule_value)


def fused_adam(
    learning_rate=1e-3,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    adam_w_mode: bool = True,
    bias_correction: bool = True,
    chunk_size: int = None,  # explicit value implies layout='chunked'
    layout: str = "auto",
) -> optax.GradientTransformation:
    def adam_math(g, p, m, v, count):
        step = count.astype(jnp.float32)
        if not adam_w_mode and weight_decay:
            g = g + weight_decay * p
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        if bias_correction:
            m_hat = m / (1.0 - b1 ** step)
            v_hat = v / (1.0 - b2 ** step)
        else:
            m_hat, v_hat = m, v
        update = m_hat / (jnp.sqrt(v_hat) + eps)
        if adam_w_mode and weight_decay:
            update = update + weight_decay * p
        lr = schedule_value(learning_rate, count)
        return p - lr * update, m, v

    if resolve_layout(layout, chunk_size) == "per_tensor":
        def leaf_kernel(g, p, bufs, scal, count, stats):
            new_p, m, v = adam_math(g, p, bufs["m"], bufs["v"], count)
            return new_p, {"m": m, "v": v}, scal

        return make_per_tensor_transform(
            name="fused_adam", state_buffers=("m", "v"), leaf_kernel=leaf_kernel)

    def kernel(g, p, buffers, scalars, count, layout_):
        new_p, m, v = adam_math(g, p, buffers["m"], buffers["v"], count)
        return new_p, {"m": m, "v": v}, scalars

    return make_fused_transform(
        name="fused_adam", state_buffers=("m", "v"), kernel=kernel, chunk_size=chunk_size or mt.DEFAULT_CHUNK
    )


# Apex-style alias
FusedAdam = fused_adam
