"""Vocab-parallel softmax cross-entropy.

Re-design of ``apex/transformer/tensor_parallel/cross_entropy.py:23-103``.
The algorithm ports directly — it is three collectives over the tp axis:

1. ``pmax`` of per-shard logit maxima (reference ``all_reduce(MAX)``, :29);
2. ``psum`` of the target logit, where only the shard owning the target id
   contributes (reference masked gather + all_reduce, :40-58);
3. ``psum`` of per-shard ``sum(exp)`` (reference :60-66).

Backward computes the reference's gradient (``:80-99``):
``d logits = (softmax - onehot_masked) * dloss`` on each shard, with label
smoothing exactly as the reference's ``label_smoothing`` branch computes
it — but from (logits, max, sum_exp) residuals with the softmax recomputed
in the backward pass (the ops/xentropy.py memory design) rather than the
reference's saved fp32 softmax.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from apex_tpu.ops import _backend
from apex_tpu.ops.pallas import xentropy as _xk
from apex_tpu.parallel import mesh as mesh_lib


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def vocab_parallel_cross_entropy(
    logits: jax.Array,
    target: jax.Array,
    label_smoothing: float = 0.0,
    axis_name: str = mesh_lib.TENSOR_AXIS,
    impl: str = "auto",
) -> jax.Array:
    """Per-token loss; ``logits`` are this shard's (..., V/tp) slice, target
    is the *global* token id. Must run inside shard_map with ``axis_name``.
    ``impl``: auto|pallas|xla — dispatch of the fused statistics kernel,
    the per-op override convention shared with the other fused ops."""
    loss, _ = _vce_fwd(logits, target, label_smoothing, axis_name, impl)
    return loss


def _shard_info(logits, axis_name):
    per = logits.shape[-1]
    if axis_name is None:
        return per, jnp.zeros((), jnp.int32)
    rank = jax.lax.axis_index(axis_name)
    return per, rank * per


def _vce_fwd(logits, target, label_smoothing, axis_name, impl="auto"):
    per, start = _shard_info(logits, axis_name)
    psum = (lambda v: v) if axis_name is None else (lambda v: jax.lax.psum(v, axis_name))
    pmax = (lambda v: v) if axis_name is None else (lambda v: jax.lax.pmax(v, axis_name))

    local_t = target - start
    in_shard = (local_t >= 0) & (local_t < per)
    t_idx = jnp.where(in_shard, local_t, 0)

    n = 1
    for d in logits.shape[:-1]:
        n *= d
    vocab = per * (1 if axis_name is None else jax.lax.axis_size(axis_name))
    # the Mosaic dialect has no f16: strict-fp16 logits take the jnp path
    use_kernel = _backend.choose_impl(
        impl, _xk.shapes_ok(n, per) and logits.dtype != jnp.float16
    ) == "pallas"
    if use_kernel:
        # One blockwise pass over the bf16/fp32 logits gives the per-row
        # (max, exp-sum, target-logit, row-sum) stats without the full-size
        # fp32 ``logits - max`` temporary the jnp formulation materializes
        # (it has three consumers, so XLA stages it: ~2 GB and ~5 ms/step of
        # HBM traffic on the flagship bench). Out-of-shard labels contribute
        # 0 to the target stat inside the kernel — the masked-gather psum of
        # the reference (:40-58) falls out for free.
        m_loc, l_loc, t_raw, s_raw = _xk.xent_stats(
            logits.reshape(n, per), local_t.reshape(n),
            interpret=_backend.interpret_mode(),
        )
        stats_shape = logits.shape[:-1]
        m_loc = m_loc.reshape(stats_shape)
        m = pmax(m_loc)
        sum_exp = psum(l_loc.reshape(stats_shape) * jnp.exp(m_loc - m))
        # rebase the raw target logit to the global max *on the owning shard
        # only*: a label no shard owns (ignore/padding sentinel) must yield
        # t_logit == 0, matching the jnp path's masked gather
        t_logit = psum(t_raw.reshape(stats_shape) - jnp.where(in_shard, m, 0.0))
        sum_logits = (psum(s_raw.reshape(stats_shape)) - vocab * m
                      if label_smoothing > 0 else None)
    else:
        lf = logits.astype(jnp.float32)

        # 1. global max for stability
        m = pmax(jnp.max(lf, axis=-1))
        lf = lf - m[..., None]

        # 2. target logit: only the owning shard contributes
        t_logit = jnp.take_along_axis(lf, t_idx[..., None], axis=-1)[..., 0]
        t_logit = psum(jnp.where(in_shard, t_logit, 0.0))

        # 3. global sum-exp
        sum_exp = psum(jnp.sum(jnp.exp(lf), axis=-1))
        sum_logits = psum(jnp.sum(lf, axis=-1)) if label_smoothing > 0 else None

    log_sum_exp = jnp.log(sum_exp)
    loss = log_sum_exp - t_logit
    if label_smoothing > 0:
        # reference's smoothing branch (:68-77): loss = (1-ε)·nll + ε/V · Σ nll_i
        smooth = label_smoothing / vocab
        loss = (1.0 - label_smoothing) * loss + smooth * (
            vocab * log_sum_exp - sum_logits
        )

    # Residuals: the input logits (aliasing the unembedding gemm's output —
    # no extra (..., V/tp) write) plus the O(tokens) stats; backward
    # recomputes the softmax the way ops/xentropy.py does. Saving the fp32
    # softmax instead would add a residual 2× the logits' size at bf16 and a
    # full extra HBM pass to write it.
    return loss, (logits, m, sum_exp, in_shard, t_idx)


def _vce_bwd(label_smoothing, axis_name, impl, res, dloss):
    del impl  # backward recomputes from residuals; no kernel dispatch
    logits, m, sum_exp, in_shard, t_idx = res
    per = logits.shape[-1]
    sf = jnp.exp(logits.astype(jnp.float32) - m[..., None]) / sum_exp[..., None]
    onehot = jax.nn.one_hot(t_idx, per, dtype=jnp.float32) * in_shard[..., None]
    if label_smoothing > 0:
        vocab = per * (1 if axis_name is None else jax.lax.axis_size(axis_name))
        grad = sf - (1.0 - label_smoothing) * onehot - label_smoothing / vocab
    else:
        grad = sf - onehot
    return (grad * dloss[..., None]).astype(logits.dtype), None


vocab_parallel_cross_entropy.defvjp(_vce_fwd, _vce_bwd)


def cross_entropy_with_grad(logits, target, dloss, label_smoothing=0.0,
                            axis_name=mesh_lib.TENSOR_AXIS, impl="auto"):
    """(per-token loss, ``dloss`` times its gradient with respect to
    ``logits``, in their dtype) from one set of logits:
    :func:`vocab_parallel_cross_entropy`'s two rules run back to back, for a
    caller that knows the loss's cotangent ``dloss`` (logits.shape[:-1]) where
    it makes the logits and so need not keep them, or make them again, for a
    backward pass. Nothing here is differentiated."""
    loss, res = _vce_fwd(logits, target, label_smoothing, axis_name, impl)
    return loss, _vce_bwd(label_smoothing, axis_name, impl, res, dloss)[0]


def masked_mean(losses: jax.Array, loss_mask=None) -> jax.Array:
    """Mean per-token loss, optionally weighted by a 0/1 ``loss_mask``
    (1 = count) — the reduction every loss head shares (reference
    ``pipeline_parallel/utils.py:303``: EOD/padding positions excluded
    the same way). The 1.0 denominator floor keeps an all-masked batch
    finite (loss 0) instead of NaN."""
    if loss_mask is None:
        return jnp.mean(losses)
    m = loss_mask.astype(losses.dtype)
    return jnp.sum(losses * m) / jnp.maximum(jnp.sum(m), 1.0)
