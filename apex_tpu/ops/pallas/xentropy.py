"""Pallas fused cross-entropy statistics kernel.

One blockwise pass over the (tokens, vocab-shard) logits computing, per row:
the max, the exp-sum relative to that max (online-softmax recurrence, same as
the attention kernels), the raw logit at the target column, and the raw row
sum (label smoothing). This is the TPU replacement for the fp32 staging pass
the XLA formulation materializes: with bf16 logits the jnp path writes a
full-size fp32 ``logits - max`` temporary (~2 GB on the flagship bench, ~5 ms
of pure HBM traffic per step) because the converted tensor has three
consumers; the kernel reads the bf16 logits once and writes only O(tokens)
statistics.

Role parity: ``apex/contrib/csrc/xentropy/xentropy_kernel.cu`` fuses the same
softmax statistics into its cross-entropy forward.

Out-of-range labels contribute 0 to the target stat — exactly the masked
gather the vocab-parallel algorithm needs (the owning shard is the only one
whose column range contains the label), so the caller psums the stat across
shards without any extra masking.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.pallas import exact_block

NEG_INF = -1e30
_LANES = 8  # row-stat carrier lanes (cf. attention._LSE_LANES)


def shapes_ok(n: int, v: int) -> bool:
    return exact_block(n, 256, 8) > 0 and exact_block(v, 2048, 128) > 0


def _stats_kernel(x_ref, lab_ref, m_ref, l_ref, t_ref, s_ref,
                  m_scr, l_scr, t_scr, s_scr, *, bv, nv):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        t_scr[:] = jnp.zeros_like(t_scr)
        s_scr[:] = jnp.zeros_like(s_scr)

    x = x_ref[:].astype(jnp.float32)  # (bn, bv)
    bn = x.shape[0]
    m_new = jnp.maximum(m_scr[:], jnp.max(x, axis=1, keepdims=True))
    alpha = jnp.exp(m_scr[:] - m_new)
    l_scr[:] = l_scr[:] * alpha + jnp.sum(jnp.exp(x - m_new), axis=1,
                                          keepdims=True)
    m_scr[:] = m_new
    cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    hit = cols == lab_ref[:, 0:1]
    t_scr[:] += jnp.sum(jnp.where(hit, x, 0.0), axis=1, keepdims=True)
    s_scr[:] += jnp.sum(x, axis=1, keepdims=True)

    @pl.when(j == nv - 1)
    def _finish():
        shape = (bn, _LANES)
        m_ref[:] = jnp.broadcast_to(m_scr[:], shape)
        l_ref[:] = jnp.broadcast_to(l_scr[:], shape)
        t_ref[:] = jnp.broadcast_to(t_scr[:], shape)
        s_ref[:] = jnp.broadcast_to(s_scr[:], shape)


def xent_stats(logits2d, labels, *, interpret=False):
    """(N, V) logits + (N,) int labels -> per-row fp32 stats
    ``(max, sumexp_rel_max, target_logit_raw, row_sum_raw)``; labels outside
    ``[0, V)`` yield ``target_logit_raw == 0``."""
    n, v = logits2d.shape
    bn = exact_block(n, 256, 8)
    bv = exact_block(v, 2048, 128)
    if not bn or not bv:
        raise ValueError(f"untileable ({n}, {v}) for the xent stats kernel")
    nv = v // bv
    lab8 = jnp.broadcast_to(labels.astype(jnp.int32)[:, None], (n, _LANES))

    stat = jax.ShapeDtypeStruct((n, _LANES), jnp.float32)
    m, l, t, s = pl.pallas_call(
        functools.partial(_stats_kernel, bv=bv, nv=nv),
        name="xentropy_stats",
        grid=(n // bn, nv),
        in_specs=[
            pl.BlockSpec((bn, bv), lambda i, j: (i, j)),
            pl.BlockSpec((bn, _LANES), lambda i, j: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((bn, _LANES), lambda i, j: (i, 0))] * 4,
        out_shape=[stat] * 4,
        scratch_shapes=[pltpu.VMEM((bn, 1), jnp.float32)] * 4,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(logits2d, lab8)
    return m[:, 0], l[:, 0], t[:, 0], s[:, 0]
