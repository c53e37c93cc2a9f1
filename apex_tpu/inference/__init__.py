"""Inference: KV-cached autoregressive decode + encoder serving.

The serving tier the training-only reference never had (ROADMAP north
star: "serves heavy traffic from millions of users"). Three pieces:

* :class:`~apex_tpu.inference.engine.DecodeEngine` — batched generation
  for the flagship GPT: pre-allocated donated KV cache in the
  attention-native ``(layers, batch, kv_heads, max_s, head_dim)`` layout,
  jit'd ``prefill`` (reuses the flash-attention training forward) and a
  ``decode_step`` that compiles ONCE (stable avals, in-place
  ``dynamic_update_slice`` cache writes) — greedy, temperature, and
  top-k sampling;
* :func:`~apex_tpu.inference.engine.jit_encoder` — BERT-style encoder
  serving (stable-aval jit of the training forward; encoders need no
  cache);
* :func:`~apex_tpu.inference.sampling.sample_logits` — the sampling
  primitive.

``DecodeEngine.generate(..., draft=...)`` speculates: a
:class:`~apex_tpu.spec.drafter.Drafter` proposes a static k tokens per
round, one ``spec_verify_step`` scores all k+1 positions, and the fused
verify tail (:func:`apex_tpu.ops.fused_verify`) accepts the longest
valid prefix — greedy output token-identical to ``draft=None``, with
:class:`~apex_tpu.inference.engine.SpecStats` accounting acceptance
(``bench.py --spec`` measures the speedup).

The fused decode-attention op lives in
:func:`apex_tpu.ops.decode_attention` (Pallas kernel + XLA fallback);
the cached model math behind the layer-math seam both engines' step
bodies are written against (:class:`apex_tpu.serving.engine.ModelMath`
over :class:`apex_tpu.models.GPTModel`'s projections). Serving
throughput is measured by ``python bench.py --decode`` (see
``docs/api/inference.md`` for the cache-layout and HBM-bound analysis).

This engine decodes ONE fixed batch in lockstep; serving mixed traffic
— requests of different lengths arriving at different times — lives one
layer up in :mod:`apex_tpu.serving` (continuous batching over a paged
block-pool cache, chunked prefill, fused sampling tail), which reuses
this module's decode math and sampling primitives.
"""

from apex_tpu.inference.engine import (  # noqa: F401
    DecodeEngine,
    SpecStats,
    jit_encoder,
)
from apex_tpu.inference.sampling import sample_logits  # noqa: F401
