"""Rotary position embedding on part of each head.

``x`` (batch, seq, heads, head_dim): the first ``rotary_dim`` features of
every head are rotated by their position (the two halves of that slice
against each other, the convention of the published decoders that use
``partial_rotary_factor``); the rest pass through. Angles in float32,
output in ``x``'s dtype.
"""

from __future__ import annotations

import jax.numpy as jnp


def rotary_angles(positions, rotary_dim: int, theta: float):
    """(cos, sin), each (seq, rotary_dim // 2) float32."""
    inv = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def apply_partial_rotary(x, rotary_dim: int, theta: float = 10000.0, positions=None):
    if rotary_dim % 2 or rotary_dim > x.shape[-1]:
        raise ValueError(f"rotary_dim {rotary_dim} must be even and at most "
                         f"head_dim {x.shape[-1]}")
    if positions is None:
        positions = jnp.arange(x.shape[1])
    cos, sin = (a[None, :, None, :] for a in rotary_angles(positions, rotary_dim, theta))
    half = rotary_dim // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:rotary_dim].astype(jnp.float32)
    out = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    return jnp.concatenate([a.astype(x.dtype) for a in out] + [x[..., rotary_dim:]], axis=-1)
