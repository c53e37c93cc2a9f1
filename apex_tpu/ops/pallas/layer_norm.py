"""Pallas LayerNorm / RMSNorm forward+backward kernels.

TPU-native equivalent of ``fused_layer_norm_cuda``
(``csrc/layer_norm_cuda_kernel.cu``; exports ``csrc/layer_norm_cuda.cpp:429-441``).
Same contract as the CUDA kernels: forward emits (y, mean, rstd) so backward
never recomputes the reduction; backward emits dx plus fully reduced
(dgamma, dbeta) — accumulated in-kernel across the sequential row-block grid
into one revisited output block, replacing the CUDA version's two-stage
``cuComputePartGradGammaBeta``/``cuComputeGradGammaBeta`` reduction.

Layout: inputs are viewed as (rows, hidden); one grid step owns a
(block_rows, hidden) tile, reductions run on the VPU along the lane axis.
All statistics math is fp32 regardless of input dtype (the kernels'
``U = float`` accumulator type).

Constraints (checked by the caller): hidden % 128 == 0 and the whole
(block_rows, hidden) fp32 tile must fit VMEM; rows are padded by Pallas.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pick_block_rows(rows: int, hidden: int, vmem_budget: int = 2 * 1024 * 1024) -> int:
    """Largest power-of-two row block whose fp32 tile fits the VMEM budget."""
    br = max(8, min(512, vmem_budget // (hidden * 4)))
    # round down to a power of two >= 8
    p = 8
    while p * 2 <= br:
        p *= 2
    return p


# --- forward ------------------------------------------------------------------

def _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps, rms):
    x = x_ref[:].astype(jnp.float32)
    if rms:
        mean = jnp.zeros((x.shape[0], 1), jnp.float32)
        xc = x
    else:
        mean = jnp.mean(x, axis=1, keepdims=True)
        xc = x - mean
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    y = xhat
    if w_ref is not None:
        y = y * w_ref[:].astype(jnp.float32)
    if b_ref is not None:
        y = y + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def _pad_rows(a, br):
    """Zero-pad the row axis to a block multiple: Pallas pads partial input
    blocks with *undefined* data, which would poison the in-kernel
    reductions; explicit zeros are inert in every reduction below."""
    rows = a.shape[0]
    pad = (-rows) % br
    return jnp.pad(a, ((0, pad), (0, 0))) if pad else a


def ln_fwd(x2d, weight, bias, *, eps: float, rms: bool, interpret: bool):
    """x2d: (rows, hidden). Returns (y, mean(rows,1), rstd(rows,1)) fp32 stats."""
    rows, hidden = x2d.shape
    br = _pick_block_rows(rows, hidden)
    x2d = _pad_rows(x2d, br)
    rows_p = x2d.shape[0]
    grid = (rows_p // br,)
    base = functools.partial(_ln_fwd_kernel, eps=eps, rms=rms)
    if weight is None and bias is not None:
        raise ValueError("bias without weight is not supported")

    in_specs = [pl.BlockSpec((br, hidden), lambda i: (i, 0))]
    args = [x2d]
    # affine params ride as (1, hidden): flat 1D bf16 operands hit a
    # Mosaic/XLA sublane-packing layout mismatch on real TPU hardware
    if weight is not None:
        in_specs.append(pl.BlockSpec((1, hidden), lambda i: (0, 0)))
        args.append(weight.reshape(1, hidden))
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, hidden), lambda i: (0, 0)))
        args.append(bias.reshape(1, hidden))
    # explicit positional signatures: Pallas passes inputs then outputs
    # positionally, so absent refs must vanish from the signature entirely
    if weight is not None and bias is not None:
        kernel = base
    elif weight is not None:
        kernel = lambda x, w, y, m, r: base(x, w, None, y, m, r)  # noqa: E731
    else:
        kernel = lambda x, y, m, r: base(x, None, None, y, m, r)  # noqa: E731

    y, mean, rstd = pl.pallas_call(
        kernel,
        name="ln_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((br, hidden), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows_p, hidden), x2d.dtype),
            jax.ShapeDtypeStruct((rows_p, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows_p, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return y[:rows], mean[:rows], rstd[:rows]


# --- backward -----------------------------------------------------------------

def _ln_bwd_kernel(
    dy_ref, x_ref, mean_ref, rstd_ref, w_ref,
    dx_ref, dw_ref, db_ref, *, rms, has_affine,
):
    dy = dy_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]
    if rms:
        xhat = x * rstd
    else:
        xhat = (x - mean_ref[:]) * rstd
    if has_affine:
        w = w_ref[:].astype(jnp.float32)
        dyw = dy * w
        # dgamma/dbeta accumulate across the sequential grid into one
        # revisited output block (the CUDA version's two-stage
        # cuComputePartGradGammaBeta/cuComputeGradGammaBeta reduction)
        @pl.when(pl.program_id(0) == 0)
        def _init():
            dw_ref[:] = jnp.zeros_like(dw_ref)
            db_ref[:] = jnp.zeros_like(db_ref)

        dw_ref[:] += jnp.sum(dy * xhat, axis=0, keepdims=True)
        db_ref[:] += jnp.sum(dy, axis=0, keepdims=True)
    else:
        dyw = dy
    h = x.shape[1]
    c2 = jnp.sum(dyw * xhat, axis=1, keepdims=True) / h
    if rms:
        dx = (dyw - xhat * c2) * rstd
    else:
        c1 = jnp.sum(dyw, axis=1, keepdims=True) / h
        dx = (dyw - c1 - xhat * c2) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)


def ln_bwd(dy2d, x2d, mean, rstd, weight, *, rms: bool, interpret: bool):
    """Returns (dx, dweight|None, dbias|None); dweight/dbias fp32."""
    rows, hidden = x2d.shape
    br = _pick_block_rows(rows, hidden)
    dy2d, x2d = _pad_rows(dy2d, br), _pad_rows(x2d, br)
    mean, rstd = _pad_rows(mean, br), _pad_rows(rstd, br)
    rows_p = x2d.shape[0]
    nblocks = rows_p // br
    has_affine = weight is not None
    base = functools.partial(_ln_bwd_kernel, rms=rms, has_affine=has_affine)

    in_specs = [
        pl.BlockSpec((br, hidden), lambda i: (i, 0)),
        pl.BlockSpec((br, hidden), lambda i: (i, 0)),
        pl.BlockSpec((br, 1), lambda i: (i, 0)),
        pl.BlockSpec((br, 1), lambda i: (i, 0)),
    ]
    args = [dy2d, x2d, mean, rstd]
    if has_affine:
        in_specs.append(pl.BlockSpec((1, hidden), lambda i: (0, 0)))
        args.append(weight.reshape(1, hidden))
        kernel = base
    else:
        kernel = lambda dy, x, m, r, dx, dwp, dbp: base(  # noqa: E731
            dy, x, m, r, None, dx, dwp, dbp
        )

    dx, dw, db = pl.pallas_call(
        kernel,
        name="ln_bwd",
        grid=(nblocks,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((br, hidden), lambda i: (i, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows_p, hidden), x2d.dtype),
            jax.ShapeDtypeStruct((1, hidden), jnp.float32),
            jax.ShapeDtypeStruct((1, hidden), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(*args)
    if has_affine:
        return dx[:rows], dw[0], db[0]
    return dx[:rows], None, None
