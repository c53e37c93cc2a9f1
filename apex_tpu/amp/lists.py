"""Declarative per-op cast policy tables (opt-level O1 semantics).

The reference implements O1 by monkey-patching every listed function in
``torch``/``torch.Tensor``/``torch.nn.functional`` with cast wrappers
(``apex/amp/amp.py:68-177``, ``apex/amp/wrap.py``). The *policy* lives in
tables (``apex/amp/lists/functional_overrides.py``, ``torch_overrides.py``,
``tensor_overrides.py``). JAX has no mutable op namespace to patch — and XLA
already promotes correctly — so we keep only the tables, expressed over
abstract op families, and expose:

* :func:`op_cast_dtype` — the dtype a policy-aware layer should compute a
  given op family in. Layers in ``apex_tpu.ops`` consult this when the ambient
  policy has ``per_op_rules=True``.
* registries mirroring ``amp.register_half_function`` /
  ``register_float_function`` / ``register_promote_function``
  (``apex/amp/amp.py:30-64``) so user code can extend the tables.

Op families (not individual functions — JAX composes from primitives):

* HALF  (run in compute dtype): matmul-shaped ops — conv, dense, attention
  (cf. FP16 lists: ``lists/functional_overrides.py:17-26``,
  ``torch_overrides.py:7-27``).
* FLOAT (run in fp32): softmax, normalization, losses, transcendentals,
  reductions (cf. FP32 lists: ``functional_overrides.py:28-67``,
  ``torch_overrides.py:29-60``).
* PROMOTE (widest input dtype): multi-arg math, concat/stack
  (``torch_overrides.py:81-111``) — this is XLA's native promotion; listed for
  completeness and for the checker.
* BANNED: ops numerically unsafe in half precision regardless
  (``functional_overrides.py:69-80`` bans ``binary_cross_entropy``) —
  :func:`check_banned` raises with the same guidance.
"""

from __future__ import annotations

import jax.numpy as jnp

HALF_OPS = {
    # matmul/conv family → MXU, compute dtype
    "conv", "conv1d", "conv2d", "conv3d", "conv_transpose",
    "dense", "linear", "matmul", "bmm", "einsum", "attention", "mlp",
    # RNN cells are gate matmuls (cf. wrap.rnn_cast / rnn_compat,
    # apex/amp/wrap.py:157-265 — the reference casts weights+inputs half)
    "rnn", "lstm", "gru",
    # the chunked gated delta rule is matmuls over chunk operands; its
    # decays, norms and state are fp32 inside whatever the inputs
    "gated_delta_rule",
    # the same rule with a decay a key channel
    "kda_rule",
    # the chunked state-space scan likewise (decays and state fp32 inside)
    "ssd_scan",
}

FLOAT_OPS = {
    # numerically sensitive → fp32
    "softmax", "log_softmax", "layer_norm", "rms_norm", "batch_norm",
    "group_norm", "cross_entropy", "nll_loss", "mse_loss", "l1_loss",
    "smooth_l1_loss", "kl_div", "cosine_similarity", "focal_loss",
    "exp", "log", "log1p", "pow", "erf", "erfinv", "softplus",
    "sum", "prod", "cumsum", "cumprod", "norm", "mean", "var", "std",
}

PROMOTE_OPS = {
    "add", "sub", "mul", "div", "addcmul", "addcdiv",
    "cat", "stack", "concatenate", "where", "equal", "dot",
}

BANNED_OPS = {
    # fp16-unsafe even with scaling; reference raises and points users at the
    # fused fp32 alternative (functional_overrides.py:69-80)
    "binary_cross_entropy": (
        "binary_cross_entropy on half inputs is numerically unsafe; compute "
        "the loss in fp32 (policy.cast_to_output) or use "
        "sigmoid_cross_entropy_with_logits"
    ),
}


def register_half_op(name: str) -> None:
    """cf. ``amp.register_half_function`` / ``@amp.half_function``
    (``apex/amp/amp.py:30-40``; used e.g. by ``apex/mlp/mlp.py:24``)."""
    FLOAT_OPS.discard(name)
    HALF_OPS.add(name)


def register_float_op(name: str) -> None:
    HALF_OPS.discard(name)
    FLOAT_OPS.add(name)


def register_promote_op(name: str) -> None:
    HALF_OPS.discard(name)
    FLOAT_OPS.discard(name)
    PROMOTE_OPS.add(name)


def _ref_spelling(register):
    """Reference-spelling wrappers: ``amp.register_half_function(module,
    'fn')`` (``apex/amp/__init__.py``) keys on a (module, name) pair because
    it must monkey-patch the module; the op-rule tables key on the op name
    alone, so the module argument is accepted and ignored."""

    def wrapper(module_or_name, function_name: str | None = None) -> None:
        register(function_name if function_name is not None else module_or_name)

    wrapper.__doc__ = _ref_spelling.__doc__
    return wrapper


register_half_function = _ref_spelling(register_half_op)
register_float_function = _ref_spelling(register_float_op)
register_promote_function = _ref_spelling(register_promote_op)


_HALF_DTYPES = (jnp.float16, jnp.bfloat16)


def check_banned(name: str, *input_dtypes) -> None:
    """Raise for fp16-unsafe ops — only when half inputs are actually
    present, matching ``wrap.err_if_any_half`` (``apex/amp/wrap.py:114-130``,
    which runs the original op untouched when no arg is half)."""
    if name in BANNED_OPS and (
        not input_dtypes or any(dt in _HALF_DTYPES for dt in input_dtypes)
    ):
        raise RuntimeError(f"amp: {BANNED_OPS[name]}")


def op_cast_dtype(op: str, policy, *input_dtypes):
    """Dtype an O1-style policy computes ``op`` in.

    HALF → ``policy.compute_dtype``; FLOAT → fp32; PROMOTE/unknown → widest
    input dtype (matching ``wrap.promote``'s ``maybe_float`` behavior,
    ``apex/amp/wrap.py:65-90``).
    """
    if not getattr(policy, "per_op_rules", False):
        return policy.compute_dtype
    check_banned(op, *input_dtypes)
    if op in HALF_OPS:
        return policy.compute_dtype
    if op in FLOAT_OPS:
        return jnp.float32
    if input_dtypes:
        return jnp.result_type(*input_dtypes)
    return policy.compute_dtype


def _is_float_array(a) -> bool:
    return (
        a is not None
        and hasattr(a, "dtype")
        and jnp.issubdtype(a.dtype, jnp.floating)
    )


def apply_op_rules(op: str, *arrays, policy=None):
    """Cast ``arrays`` to the dtype the ambient O1 policy assigns ``op``.

    This is the call-site half of the reference's cast wrappers
    (``make_cast_wrapper`` ``apex/amp/wrap.py:10-29`` for HALF/FLOAT ops,
    ``promote`` ``wrap.py:65-90``, ``err_if_any_half`` ``wrap.py:114-130``):
    every ``apex_tpu.ops`` entry point routes its floating inputs through
    here. Identity unless the ambient policy has ``per_op_rules`` (O1), so
    O0/O2/O3 pay nothing. Non-float leaves (int labels/tokens) and ``None``
    pass through untouched.

    The reference's fp16 weight cache (``utils.cached_cast``, invalidated
    per-iteration via ``_amp_state.handle._clear_cache``) has no analog here
    by design: under ``jit`` the cast is a traced op that XLA CSEs, so
    repeated casts of the same weight cost nothing at runtime.
    """
    if policy is None:
        from apex_tpu.amp.policy import current_policy

        policy = current_policy()
    if not getattr(policy, "per_op_rules", False):
        return arrays
    in_dtypes = [a.dtype for a in arrays if _is_float_array(a)]
    target = op_cast_dtype(op, policy, *in_dtypes)
    return tuple(
        a.astype(target) if _is_float_array(a) and a.dtype != target else a
        for a in arrays
    )
