"""Fused sampling tail: logits → temperature/top-k/top-p → token.

The op-level wrapper over :mod:`apex_tpu.ops.pallas.sampling` following
the house dispatch rule (:mod:`apex_tpu.ops._backend`): the Pallas kernel
on TPU when the vocab tiles the lane dim, interpret-mode Pallas under
``APEX_TPU_PALLAS=interpret``, and an XLA composition otherwise. The XLA
fallback calls the SAME module-level filter/sample helpers the kernel
body runs, so the two paths agree token-for-token on shared noise — the
parity anchor ``tests/test_serving.py`` pins.

This is the serving engines' tail (one fused dispatch per decode step);
the standalone, sort/cumsum-formulated sampler for ad-hoc use stays in
:func:`apex_tpu.inference.sampling.sample_logits`.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.ops import _backend
from apex_tpu.ops.pallas.sampling import (_ROWS, filtered_scaled,
                                          fused_sample_fwd, gumbel_argmax,
                                          whole_rows_fit)


def sample_kernel_ok(vocab: int, dtype) -> bool:
    """Mosaic eligibility: the vocab is the lane dim of every whole-row
    reduction, so it must be a 128-multiple; f16 has no Mosaic support;
    and one grid step's whole rows must fit the VMEM budget."""
    return (vocab % 128 == 0 and dtype != jnp.float16
            and whole_rows_fit(_ROWS, vocab))


def fused_sample(logits: jax.Array, key: Optional[jax.Array] = None, *,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, impl: str = "auto") -> jax.Array:
    """(b, V) logits → (b,) int32 tokens through ONE fused tail.

    ``temperature == 0`` is greedy argmax (already a single reduction —
    no kernel needed, ``top_k``/``top_p`` are no-ops on an argmax).
    Otherwise: scale by ``1/temperature``, keep the ``top_k`` largest
    (0 = all), then the minimal top-``top_p`` probability mass (1.0 =
    all; ties at either threshold are kept), and draw via Gumbel-argmax
    on a uniform row folded from ``key``. All knobs are STATIC — they
    select the compiled program, never retrace per step.

    The uniform noise is drawn inside the caller's jit by ``jax.random``
    (one fused producer) and consumed by the kernel in the same program;
    kernel and XLA fallback share it, so ``impl`` never changes the
    sampled token.
    """
    if logits.ndim != 2:
        raise ValueError(f"fused_sample takes (b, V) logits; got "
                         f"{logits.shape}")
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if key is None:
        raise ValueError("temperature > 0 sampling requires a PRNG key")
    b, V = logits.shape
    top_k = min(int(top_k), V)
    # (0, 1]: tiny floor keeps log(u) finite (u=0 would pin a token's
    # Gumbel at -inf, silently excluding it)
    u = jax.random.uniform(key, (b, V), jnp.float32,
                           minval=jnp.finfo(jnp.float32).tiny, maxval=1.0)
    ok = sample_kernel_ok(V, logits.dtype)
    if _backend.choose_impl(impl, ok) == "pallas":
        return fused_sample_fwd(logits, u, temperature=float(temperature),
                                top_k=top_k, top_p=float(top_p),
                                interpret=_backend.interpret_mode())
    s = filtered_scaled(logits, temperature=float(temperature),
                        top_k=top_k, top_p=float(top_p))
    return gumbel_argmax(s, u).astype(jnp.int32)
