"""Pallas scaled-masked-softmax kernels (forward + backward-from-probs).

TPU-native equivalent of ``scaled_masked_softmax_cuda`` and
``scaled_upper_triang_masked_softmax_cuda``
(``csrc/megatron/scaled_masked_softmax.h``, ``scaled_upper_triang_masked_softmax.h``).
Contract matches the CUDA warp kernels: forward computes
``softmax(scale * x + mask)`` with the mask applied as a -10000 additive fill
(boolean mask) or a built-in causal triangle; backward consumes the *saved
probabilities*: ``dx = scale * y * (dy - sum(dy * y))``.

Layout: logits viewed as (rows, sk); one grid step owns (block_rows, sk) in
VMEM. The causal variant derives its row's global query index from the grid
position, so sq never has to fit in one block. Unlike the CUDA kernels there
is no ``16 < sk <= 2048`` cap — blocks just need sk % 128 == 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

MASK_FILL = -10000.0  # matches the CUDA kernels' masked fill value


def _pick_block_rows(sk: int, vmem_budget: int = 2 * 1024 * 1024) -> int:
    br = max(8, min(512, vmem_budget // (sk * 4)))
    p = 8
    while p * 2 <= br:
        p *= 2
    return p


def _pad_rows(a, br):
    pad = (-a.shape[0]) % br
    return jnp.pad(a, ((0, pad), (0, 0))) if pad else a


# --- forward ------------------------------------------------------------------

def _softmax_fwd_kernel(x_ref, mask_ref, y_ref, *, scale, causal, sq):
    x = x_ref[:].astype(jnp.float32) * scale
    rows, sk = x.shape
    if causal:
        # global query index of each row in this block; rows cycle through
        # sq within each (batch*head) slab, and blocks are row-contiguous.
        i = pl.program_id(0)
        row0 = i * rows
        q_idx = (row0 + jax.lax.broadcasted_iota(jnp.int32, (rows, sk), 0)) % sq
        k_idx = jax.lax.broadcasted_iota(jnp.int32, (rows, sk), 1)
        x = jnp.where(k_idx <= q_idx, x, MASK_FILL)
    elif mask_ref is not None:
        x = jnp.where(mask_ref[:] != 0, MASK_FILL, x)
    m = jnp.max(x, axis=1, keepdims=True)
    e = jnp.exp(x - m)
    y = e / jnp.sum(e, axis=1, keepdims=True)
    y_ref[:] = y.astype(y_ref.dtype)


def softmax_fwd(x2d, mask2d, *, scale: float, causal: bool, sq: int, interpret: bool):
    """x2d: (rows, sk); mask2d: same shape (nonzero ⇒ masked) or None."""
    rows, sk = x2d.shape
    br = _pick_block_rows(sk)
    if causal:
        # keep block rows within one (batch, head) slab so q_idx math is exact
        while br > 8 and sq % br:
            br //= 2
        if sq % br:
            br = 8 if sq % 8 == 0 else 1
    x2d = _pad_rows(x2d, br)
    rows_p = x2d.shape[0]
    base = functools.partial(_softmax_fwd_kernel, scale=scale, causal=causal, sq=sq)
    in_specs = [pl.BlockSpec((br, sk), lambda i: (i, 0))]
    args = [x2d]
    if mask2d is not None and not causal:
        in_specs.append(pl.BlockSpec((br, sk), lambda i: (i, 0)))
        args.append(_pad_rows(mask2d, br))
        kernel = base
    else:
        kernel = lambda x, y: base(x, None, y)  # noqa: E731
    y = pl.pallas_call(
        kernel,
        name="softmax_fwd",
        grid=(rows_p // br,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, sk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, sk), x2d.dtype),
        interpret=interpret,
    )(*args)
    return y[:rows]


# --- backward -----------------------------------------------------------------

def _softmax_bwd_kernel(dy_ref, y_ref, dx_ref, *, scale):
    dy = dy_ref[:].astype(jnp.float32)
    y = y_ref[:].astype(jnp.float32)
    dot = jnp.sum(dy * y, axis=1, keepdims=True)
    dx_ref[:] = (scale * y * (dy - dot)).astype(dx_ref.dtype)


def softmax_bwd(dy2d, y2d, *, scale: float, interpret: bool):
    rows, sk = y2d.shape
    br = _pick_block_rows(sk)
    dy2d, y2d = _pad_rows(dy2d, br), _pad_rows(y2d, br)
    rows_p = y2d.shape[0]
    dx = pl.pallas_call(
        functools.partial(_softmax_bwd_kernel, scale=scale),
        name="softmax_bwd",
        grid=(rows_p // br,),
        in_specs=[
            pl.BlockSpec((br, sk), lambda i: (i, 0)),
            pl.BlockSpec((br, sk), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, sk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, sk), y2d.dtype),
        interpret=interpret,
    )(dy2d, y2d)
    return dx[:rows]
