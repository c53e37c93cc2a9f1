"""Static + dynamic loss scaling, as pure functions of explicit state.

Re-design of ``apex/amp/scaler.py`` (``LossScaler`` at ``:33``): dynamic
scaling starts at 2**16, doubles every 2000 overflow-free steps, halves on
overflow, clamped to [1, 2**24] (``scaler.py:38-56,197-217``). The reference
needs a fused CUDA kernel plus one D2H sync per step to learn whether grads
overflowed (``scaler.py:105-124,197-200``) and then monkey-patches
``optimizer.step`` into a one-shot skip (``apex/amp/handle.py:128-154``).

Here the whole protocol is on-device and branchless at the host level:
``all_finite`` is a fused reduction, the scale update is ``jnp.where``, and
the "skip step" is a ``jnp.where`` select between old and new params — zero
host syncs per step (better than the reference's one).

The model-parallel variant of torch's GradScaler
(``apex/transformer/amp/grad_scaler.py:38-49`` — all-reduce found_inf across
the model-parallel group) is unnecessary with global arrays: ``all_finite``
over a sharded pytree already reduces across every shard; XLA inserts the
cross-device reduction.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.monitor import spans as monitor_spans
from apex_tpu.utils.pytree import tree_all_finite

PyTree = Any


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LossScalerState:
    """Carries the scale and the overflow-free step counter.

    ``dynamic`` is static metadata (it selects the traced program, like the
    reference choosing ``LossScaler("dynamic")`` vs a constant at
    ``apex/amp/_initialize.py:227-231``).
    """

    loss_scale: jax.Array          # f32 scalar
    growth_tracker: jax.Array      # i32 scalar: unskipped steps since last growth
    skipped_steps: jax.Array       # i32 scalar: lifetime overflow count (observability)
    dynamic: bool = dataclasses.field(metadata=dict(static=True), default=True)
    growth_interval: int = dataclasses.field(metadata=dict(static=True), default=2000)
    growth_factor: float = dataclasses.field(metadata=dict(static=True), default=2.0)
    backoff_factor: float = dataclasses.field(metadata=dict(static=True), default=0.5)
    max_loss_scale: float = dataclasses.field(metadata=dict(static=True), default=2.0 ** 24)
    min_loss_scale: float = dataclasses.field(metadata=dict(static=True), default=1.0)


def init_loss_scaler(
    loss_scale: str | float = "dynamic",
    *,
    init_scale: float = 2.0 ** 16,
    growth_interval: int = 2000,
    growth_factor: float = 2.0,
    backoff_factor: float = 0.5,
    max_loss_scale: float = 2.0 ** 24,
    min_loss_scale: float = 1.0,
) -> LossScalerState:
    """Create scaler state. ``loss_scale="dynamic"`` or a fixed float, exactly
    the surface of ``amp.initialize(loss_scale=...)`` (``frontend.py:195``)."""
    dynamic = loss_scale == "dynamic"
    scale = init_scale if dynamic else float(loss_scale)
    return LossScalerState(
        loss_scale=jnp.asarray(scale, jnp.float32),
        growth_tracker=jnp.zeros((), jnp.int32),
        skipped_steps=jnp.zeros((), jnp.int32),
        dynamic=dynamic,
        growth_interval=growth_interval,
        growth_factor=growth_factor,
        backoff_factor=backoff_factor,
        max_loss_scale=max_loss_scale,
        min_loss_scale=min_loss_scale,
    )


def scale_loss(state: LossScalerState, loss: jax.Array) -> jax.Array:
    """``loss.float() * loss_scale`` (cf. ``apex/amp/handle.py:113``)."""
    return jnp.asarray(loss, jnp.float32) * state.loss_scale


def unscale_grads(state: LossScalerState, grads: PyTree) -> PyTree:
    """Unscale grads to fp32 (the reference's ``scaler.unscale`` →
    ``amp_C.multi_tensor_scale``, ``scaler.py:94-189``; here XLA fuses the
    multiply into the producing op)."""
    inv = 1.0 / state.loss_scale
    return jax.tree.map(lambda g: jnp.asarray(g, jnp.float32) * inv, grads)


def all_finite(grads: PyTree) -> jax.Array:
    """Fused overflow check (cf. inf/nan detection inside
    ``multi_tensor_scale_kernel.cu``); result stays on device."""
    return tree_all_finite(grads)


def update_loss_scaler(state: LossScalerState, grads_finite: jax.Array) -> LossScalerState:
    """Post-step scale adjustment (``scaler.py:197-217``):

    overflow → scale *= backoff (clamped at min), tracker reset;
    otherwise → tracker += 1; at growth_interval → scale *= growth (clamped).
    """
    if not state.dynamic:
        # scale is fixed, but overflow bookkeeping still runs (the reference's
        # static LossScaler also skips steps on overflow, scaler.py:76-91)
        return dataclasses.replace(
            state, skipped_steps=state.skipped_steps + jnp.where(grads_finite, 0, 1)
        )
    tracker = jnp.where(grads_finite, state.growth_tracker + 1, 0)
    grow = tracker >= state.growth_interval
    scale = jnp.where(
        grads_finite,
        jnp.where(
            grow,
            jnp.minimum(state.loss_scale * state.growth_factor, state.max_loss_scale),
            state.loss_scale,
        ),
        jnp.maximum(state.loss_scale * state.backoff_factor, state.min_loss_scale),
    )
    tracker = jnp.where(grow, 0, tracker)
    return dataclasses.replace(
        state,
        loss_scale=scale,
        growth_tracker=tracker,
        skipped_steps=state.skipped_steps + jnp.where(grads_finite, 0, 1),
    )


def scaled_value_and_grad(
    fn: Callable[..., jax.Array],
    *,
    has_aux: bool = False,
) -> Callable[..., Tuple]:
    """``value_and_grad`` with loss scaling folded in.

    ``g = scaled_value_and_grad(loss_fn)`` then
    ``(loss, (grads, finite, new_scaler)) = g(scaler_state, params, ...)``:
    the loss is scaled before differentiation, grads are unscaled to fp32, the
    finite flag and updated scaler state come back with them. This is the
    whole ``with amp.scale_loss(...)`` protocol (``apex/amp/handle.py:16-154``)
    as one pure function.
    """

    def wrapped(scaler: LossScalerState, *args, **kwargs):
        def scaled_fn(*a, **k):
            out = fn(*a, **k)
            if has_aux:
                loss, aux = out
                return scale_loss(scaler, loss), aux
            return scale_loss(scaler, out)

        with monitor_spans.span("amp/fwd_bwd"):
            if has_aux:
                (scaled, aux), grads = jax.value_and_grad(scaled_fn, has_aux=True)(*args, **kwargs)
            else:
                scaled, grads = jax.value_and_grad(scaled_fn)(*args, **kwargs)
                aux = None
        with monitor_spans.span("amp/unscale_check"):
            grads = unscale_grads(scaler, grads)
            finite = all_finite(grads)
            new_scaler = update_loss_scaler(scaler, finite)
        loss = scaled / scaler.loss_scale
        if has_aux:
            return (loss, aux), (grads, finite, new_scaler)
        return loss, (grads, finite, new_scaler)

    return wrapped


def apply_if_finite(params: PyTree, new_params: PyTree, grads_finite: jax.Array) -> PyTree:
    """Select updated params only when grads were finite — the functional form
    of the reference's one-shot ``skip_step`` patch (``handle.py:128-154``)."""
    return jax.tree.map(lambda old, new: jnp.where(grads_finite, new, old), params, new_params)


def skip_step_if_nonfinite(opt):
    """Wrap an optax optimizer so an overflowed step is skipped *entirely* —
    zero updates AND untouched inner state (momenta, step count).

    The reference's skip patch replaces ``optimizer.step`` for the overflowed
    iteration (``handle.py:128-154``), which implicitly protects the
    optimizer's exp-avg buffers from inf/nan gradients. The functional
    translation must guard both halves: ``apply_if_finite`` alone keeps
    params clean, but running ``opt.update`` with inf grads still poisons
    m/v forever. Use this wrapper whenever grads can overflow (fp16 +
    loss scaling)::

        opt = amp.skip_step_if_nonfinite(fused_adam(1e-3))
        updates, opt_state = opt.update(grads, opt_state, params)  # safe
    """
    import optax

    def init(params):
        return opt.init(params)

    def update(grads, state, params=None):
        finite = all_finite(grads)
        # sanitize before the inner update: where() keeps the old state, but
        # inf * 0 inside the unselected branch would still produce nan that
        # XLA must not see in the selected lanes
        safe_grads = jax.tree.map(
            lambda g: jnp.where(jnp.isfinite(g), g, 0).astype(g.dtype), grads
        )
        updates, new_state = opt.update(safe_grads, state, params)
        updates = jax.tree.map(
            lambda u: jnp.where(finite, u, jnp.zeros_like(u)), updates
        )
        new_state = jax.tree.map(
            lambda old, new: jnp.where(finite, new, old), state, new_state
        )
        return updates, new_state

    return optax.GradientTransformation(init, update)


# -- observability -------------------------------------------------------------

def scaler_metrics(state: LossScalerState) -> dict:
    """Host-side observability numbers for a scaler state: the loss scale,
    the growth tracker and the lifetime overflow (skipped-step) count as
    Python scalars. This is the pull point ``apex_tpu.monitor`` reads
    (``monitor.observe_scaler``) — one device→host sync, only when called."""
    return {
        "loss_scale": float(state.loss_scale),
        "growth_tracker": int(state.growth_tracker),
        "skipped_steps": int(state.skipped_steps),
    }


# -- state-dict parity (apex/amp/frontend.py:361-400) -------------------------

def state_dict(state: LossScalerState) -> dict:
    """Serializable scaler state, mirroring ``amp.state_dict()``'s per-scaler
    ``{"loss_scale": ..., "unskipped": ...}`` payload."""
    return {
        "loss_scale": float(state.loss_scale),
        "unskipped": int(state.growth_tracker),
        "skipped": int(state.skipped_steps),
        "dynamic": state.dynamic,
    }


def load_state_dict(state: LossScalerState, payload: dict) -> LossScalerState:
    return dataclasses.replace(
        state,
        loss_scale=jnp.asarray(payload["loss_scale"], jnp.float32),
        growth_tracker=jnp.asarray(payload.get("unskipped", 0), jnp.int32),
        skipped_steps=jnp.asarray(payload.get("skipped", 0), jnp.int32),
        dynamic=payload.get("dynamic", state.dynamic),
    )
