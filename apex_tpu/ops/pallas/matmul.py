"""Pallas blocked matmul with fused bias + activation epilogue.

TPU-native analog of ``fused_dense_cuda``'s cuBLASLt epilogue GEMMs
(``csrc/fused_dense_cuda.cu:10-60``) and ``mlp_cuda``'s chained GEMM+bias+act
(``csrc/mlp_cuda.cu:47-200``): one kernel computes ``act(x @ w + b)`` without
a round-trip to HBM for the intermediate. Classic MXU pattern: grid over
(M/bm, N/bn, K/bk), fp32 accumulator in VMEM scratch, epilogue applied on the
final K step.

Constraints: M, N, K multiples of the block sizes (the caller pads);
accumulation is always fp32 (``preferred_element_type``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _apply_act(r, activation):
    if activation == "none":
        return r
    if activation == "gelu":
        return jax.nn.gelu(r, approximate=True)
    if activation == "relu":
        return jnp.maximum(r, 0.0)
    if activation == "sigmoid":
        return jax.nn.sigmoid(r)
    raise ValueError(f"unknown activation {activation!r}")


def _matmul_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, activation, k_steps):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(
        x_ref[:], w_ref[:], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        r = acc_ref[:]
        if b_ref is not None:
            r = r + b_ref[:].astype(jnp.float32)  # (1, bn) broadcasts over rows
        o_ref[:] = _apply_act(r, activation).astype(o_ref.dtype)


def _round_up(v, m):
    return -(-v // m) * m


def matmul_bias_act(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array | None = None,
    *,
    activation: str = "none",
    out_dtype=None,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """``act(x @ w + b)``; x: (M, K), w: (K, N), b: (N,) or None."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    out_dtype = out_dtype or x.dtype
    bm, bn, bk = min(bm, _round_up(M, 8)), min(bn, _round_up(N, 128)), min(bk, _round_up(K, 128))
    Mp, Np, Kp = _round_up(M, bm), _round_up(N, bn), _round_up(K, bk)
    if (Mp, Kp) != (M, K):
        x = jnp.pad(x, ((0, Mp - M), (0, Kp - K)))
    if (Kp, Np) != (K, N):
        w = jnp.pad(w, ((0, Kp - K), (0, Np - N)))
    if b is not None and Np != N:
        b = jnp.pad(b, (0, Np - N))
    k_steps = Kp // bk

    base = functools.partial(_matmul_kernel, activation=activation, k_steps=k_steps)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    args = [x, w]
    if b is not None:
        # bias rides as (1, N): a flat 1D bf16 operand hits a Mosaic/XLA
        # layout mismatch ((1024)(128) vs (256)(128) sublane packing) on real
        # TPU; 2D row form tiles cleanly.
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        args.append(b.reshape(1, -1))
        kernel = base
    else:
        kernel = lambda xr, wr, orf, acc: base(xr, wr, None, orf, acc)  # noqa: E731

    out = pl.pallas_call(
        kernel,
        name="matmul_bias_act",
        grid=(Mp // bm, Np // bn, k_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*args)
    return out[:M, :N]
