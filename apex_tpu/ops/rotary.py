"""Rotary position embedding on part of each head.

``x`` (batch, seq, heads, head_dim): the first ``rotary_dim`` features of
every head are rotated by their position (the two halves of that slice
against each other, the convention of the published decoders that use
``partial_rotary_factor``); the rest pass through. Angles in float32,
output in ``x``'s dtype. ``scaling`` holds a published ``rope_scaling`` entry
of type ``yarn`` (:func:`yarn_frequencies`).
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature ``0.1 m ln(s) + 1`` (1 at ``s <= 1``)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(rotary_dim: int, theta: float, scaling):
    """(inverse frequencies (rotary_dim // 2,) float32, factor on cos and
    sin) of a ``rope_scaling`` entry of type ``yarn``: pair ``i`` keeps
    ``theta ** (-2 i / rotary_dim)`` below ``low``, takes it over ``factor``
    above ``high`` and a linear blend between — ``low`` / ``high`` the floor /
    ceiling of the pair whose wavelength fits ``beta_fast`` / ``beta_slow``
    times into ``original_max_position_embeddings``. The factor is
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``."""
    s = dict(scaling)
    if s.get("type", s.get("rope_type")) != "yarn":
        raise ValueError(f"rope_scaling of type yarn, got {s!r}")
    factor, span = s["factor"], s["original_max_position_embeddings"]
    pair = lambda turns: (rotary_dim * math.log(span / (turns * 2 * math.pi))  # noqa: E731
                          / (2 * math.log(theta)))
    low = max(math.floor(pair(s.get("beta_fast", 32))), 0)
    high = min(math.ceil(pair(s.get("beta_slow", 1))), rotary_dim - 1)
    i = jnp.arange(rotary_dim // 2, dtype=jnp.float32)
    freq = theta ** (-2.0 * i / rotary_dim)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    on_both = (yarn_mscale(factor, s.get("mscale", 1.0))
               / yarn_mscale(factor, s.get("mscale_all_dim", 0.0)))
    return freq * (1.0 - ramp) + freq / factor * ramp, on_both


def rotary_angles(positions, rotary_dim: int, theta: float, scaling=None):
    """(cos, sin), each (seq, rotary_dim // 2) float32."""
    if scaling is None:
        inv, on_both = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                                       / rotary_dim), 1.0
    else:
        inv, on_both = yarn_frequencies(rotary_dim, theta, scaling)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    if on_both == 1.0:
        return jnp.cos(ang), jnp.sin(ang)
    return jnp.cos(ang) * on_both, jnp.sin(ang) * on_both


def apply_partial_rotary(x, rotary_dim: int, theta: float = 10000.0, positions=None,
                         scaling=None):
    if rotary_dim % 2 or rotary_dim > x.shape[-1]:
        raise ValueError(f"rotary_dim {rotary_dim} must be even and at most "
                         f"head_dim {x.shape[-1]}")
    if positions is None:
        positions = jnp.arange(x.shape[1])
    cos, sin = (a[None, :, None, :]
                for a in rotary_angles(positions, rotary_dim, theta, scaling))
    half = rotary_dim // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:rotary_dim].astype(jnp.float32)
    out = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    return jnp.concatenate([a.astype(x.dtype) for a in out] + [x[..., rotary_dim:]], axis=-1)
