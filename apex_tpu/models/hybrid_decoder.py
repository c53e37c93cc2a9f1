"""A decoder whose layers take their kind from a pattern: gated delta-rule
(linear-attention) mixers and gated softmax-attention mixers in any order,
each followed by a dropless sparse-expert layer with a shared expert.

Pre-norm residual blocks (``x += mixer(norm(x)); x += experts(norm(x))``)
with zero-centred RMSNorm (``x / rms(x) * (1 + w)``), no position table
(the attention layers carry partial rotary embeddings, the delta-rule layers
need none), no biases, an untied output head. ``layer_types`` names each
layer ``"linear"`` or ``"full"``; parameters of one kind are stacked on a
leading axis under ``layers/gdn``, ``layers/attn`` and (every layer)
``layers/moe``. All linears are stored (in, out).

* ``"linear"`` — fused ``q|k|v|z`` and ``b|a`` projections, causal depthwise
  convolution + SiLU on ``q|k|v``, :func:`ops.gated_delta_rule.gated_delta_rule`
  (float32 decay, write strength, norms and state whatever the policy),
  head-wise RMSNorm gated by ``silu(z)``, output projection.
* ``"full"`` — ``q|gate`` per head, per-head RMSNorm of q and k, rotary on
  the first ``rotary_dim`` features, causal flash attention in the (batch,
  seq, heads, head_dim) layout with grouped kv heads, ``sigmoid(gate)`` on
  the context, output projection.
* experts — :func:`transformer.moe.dropless_moe_layer` over the experts held
  here (``experts_held``), router at its full width.

``loss_fn`` has ``GPTModel.loss_fn``'s signature, so
``amp.scaled_value_and_grad`` and the trainers take either model.
``float32_params`` names the leaves a mixed-precision policy should leave
in float32 (``amp.MasterWeights.create(..., keep_float32=...)``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.monitor import spans as monitor_spans
from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.gated_delta_rule import (causal_conv_silu, gated_delta_rule,
                                           gated_rms_norm)
from apex_tpu.ops.rotary import apply_partial_rotary
from apex_tpu.transformer import tensor_parallel as tp_lib
from apex_tpu.transformer.moe import dropless_moe_layer


@dataclasses.dataclass(frozen=True)
class HybridDecoderConfig:
    vocab_size: int = 32768
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = ("linear", "linear", "linear", "full")
    # softmax-attention layers
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    rotary_dim: int = 64
    rope_theta: float = 1e7
    # gated delta-rule layers
    linear_key_heads: int = 16
    linear_value_heads: int = 32
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    conv_kernel: int = 4
    # experts: the router's width, which of them are held, experts a token
    router_experts: int = 512
    experts_held: Optional[Tuple[int, int]] = None      # (first, count); None = all
    top_k: int = 10
    expert_ffn: int = 512
    shared_ffn: int = 512
    normalize_topk: bool = True
    aux_coeff: float = 1e-3
    rms_eps: float = 1e-6
    # recompute every block's two halves (mixer, experts) in the backward pass
    remat: bool = False
    attention_impl: str = "auto"
    delta_impl: str = "auto"
    experts_impl: str = "auto"
    dtype: Any = jnp.float32

    def __post_init__(self):
        bad = set(self.layer_types) - {"linear", "full"}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types holds 'linear' and 'full', got {self.layer_types!r}")
        if self.num_heads % self.num_kv_heads or self.linear_value_heads % self.linear_key_heads:
            raise ValueError("query heads must be a multiple of kv heads, and value heads "
                             "of key heads")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.router_experts)


def _norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


class HybridDecoderModel:
    float32_params = ("A_log", "dt_bias")

    def __init__(self, config: HybridDecoderConfig):
        self.config = config

    def init(self, key):
        """Random parameters (normal 0.02; residual projections scaled by
        1/sqrt(2 L); decay ``A ~ U(1, 16)``, ``dt ~ logU(1e-3, 1e-1)``)."""
        c = self.config
        H, L = c.hidden_size, len(c.layer_types)
        Lg, La = c.layer_types.count("linear"), c.layer_types.count("full")
        qk, vv = c.linear_key_heads * c.linear_key_dim, c.linear_value_heads * c.linear_value_dim
        keys = iter(jax.random.split(key, 32))
        n = lambda shape, std=0.02: (std * jax.random.normal(  # noqa: E731
            next(keys), shape, jnp.float32)).astype(c.dtype)
        res = 0.02 / (2 * L) ** 0.5
        dt = jnp.exp(jax.random.uniform(next(keys), (Lg, c.linear_value_heads), jnp.float32,
                                        jnp.log(1e-3), jnp.log(1e-1)))
        a = jax.random.uniform(next(keys), (Lg, c.linear_value_heads), jnp.float32, 1.0, 16.0)
        zeros = lambda shape: jnp.zeros(shape, c.dtype)  # noqa: E731
        Eh = c.held[1]
        return {
            "embedding": {"weight": n((c.vocab_size, H))},
            "head": {"weight": n((c.vocab_size, H))},
            "norm_f": zeros((H,)),
            "layers": {
                "norm1": zeros((L, H)), "norm2": zeros((L, H)),
                "gdn": {
                    "w_qkvz": n((Lg, H, 2 * qk + 2 * vv)), "w_ba": n((Lg, H, 2 * c.linear_value_heads)),
                    "conv_w": jax.random.uniform(next(keys), (Lg, c.conv_kernel, 2 * qk + vv),
                                                 jnp.float32, -0.5, 0.5).astype(c.dtype),
                    "A_log": jnp.log(a), "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "norm_w": jnp.ones((Lg, c.linear_value_dim), c.dtype),
                    "w_o": n((Lg, vv, H), res),
                },
                "attn": {
                    "w_q": n((La, H, 2 * c.num_heads * c.head_dim)),
                    "w_k": n((La, H, c.num_kv_heads * c.head_dim)),
                    "w_v": n((La, H, c.num_kv_heads * c.head_dim)),
                    "q_norm": zeros((La, c.head_dim)), "k_norm": zeros((La, c.head_dim)),
                    "w_o": n((La, c.num_heads * c.head_dim, H), res),
                },
                "moe": {
                    "router": n((L, H, c.router_experts)),
                    "w_gate_up": n((L, Eh, H, 2 * c.expert_ffn)),
                    "w_down": n((L, Eh, c.expert_ffn, H), res),
                    "shared_gate_up": n((L, H, 2 * c.shared_ffn)),
                    "shared_down": n((L, c.shared_ffn, H), res),
                    "shared_mix": n((L, H)),
                },
            },
        }

    # --- mixers ---------------------------------------------------------------

    def _delta_mixer(self, p, x):
        c = self.config
        b, s, _ = x.shape
        hk, hv, dk, dv = (c.linear_key_heads, c.linear_value_heads,
                          c.linear_key_dim, c.linear_value_dim)
        qkvz = jnp.dot(x, p["w_qkvz"])
        ba = jnp.dot(x, p["w_ba"], preferred_element_type=jnp.float32)
        # q|k|v and z are read where the projection left them: no slice of qkvz
        q, k, v = causal_conv_silu(qkvz, p["conv_w"], widths=(hk * dk, hk * dk, hv * dv),
                                   impl=c.delta_impl)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = (-jnp.exp(p["A_log"].astype(jnp.float32))
             * jax.nn.softplus(ba[..., hv:] + p["dt_bias"].astype(jnp.float32)))
        o = gated_delta_rule(q.reshape(b, s, hk, dk), k.reshape(b, s, hk, dk),
                             v.reshape(b, s, hv, dv), g, beta, impl=c.delta_impl)
        o = gated_rms_norm(o, qkvz, p["norm_w"], c.rms_eps, impl=c.delta_impl)
        return jnp.dot(o.reshape(b, s, hv * dv), p["w_o"])

    def _attention_mixer(self, p, x):
        c = self.config
        b, s, _ = x.shape
        nh, nkv, dh = c.num_heads, c.num_kv_heads, c.head_dim
        qg = jnp.dot(x, p["w_q"]).reshape(b, s, nh, 2 * dh)
        q, gate = qg[..., :dh], qg[..., dh:]
        k = jnp.dot(x, p["w_k"]).reshape(b, s, nkv, dh)
        v = jnp.dot(x, p["w_v"]).reshape(b, s, nkv, dh)
        q = apply_partial_rotary(_norm(q, p["q_norm"], c.rms_eps), c.rotary_dim, c.rope_theta)
        k = apply_partial_rotary(_norm(k, p["k_norm"], c.rms_eps), c.rotary_dim, c.rope_theta)
        ctx = flash_attention(q, k, v, causal=True, scale=dh ** -0.5, layout="bshd",
                              impl=c.attention_impl)
        ctx = ctx * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(ctx.dtype)
        return jnp.dot(ctx.reshape(b, s, nh * dh), p["w_o"])

    def _experts(self, p, x):
        c = self.config
        return dropless_moe_layer(
            p, x, top_k=c.top_k, experts_held=c.held, normalize_weights=c.normalize_topk,
            impl=c.experts_impl)

    # --- the stack ------------------------------------------------------------

    def hidden_states_with_aux(self, params, tokens, key=None):
        """(final hidden states, aux): ``load_balance_loss`` (mean over the
        layers), ``expert_load`` (layers, held) int32, ``dropped`` ()."""
        del key                                    # no dropout in this block
        c = self.config
        layers = params["layers"]
        with monitor_spans.span("hybrid/embed"):
            x = params["embedding"]["weight"][tokens]
        keep_plan = jax.checkpoint_policies.save_only_these_names("moe_plan")

        def mixer_half(kind, p, w, x):
            with monitor_spans.span("hybrid/gdn" if kind == "linear" else "hybrid/attn"):
                mix = self._delta_mixer if kind == "linear" else self._attention_mixer
                return x + mix(p, _norm(x, w, c.rms_eps))

        def expert_half(p, w, x):
            with monitor_spans.span("hybrid/moe"):
                y, aux = self._experts(p, _norm(x, w, c.rms_eps))
                return x + y, aux

        seen = {"linear": 0, "full": 0}
        lb, loads, dropped = 0.0, [], 0
        for i, kind in enumerate(c.layer_types):
            group = "gdn" if kind == "linear" else "attn"
            p_mix = jax.tree.map(lambda a, j=seen[kind]: a[j], layers[group])
            p_moe = jax.tree.map(lambda a, i=i: a[i], layers["moe"])
            seen[kind] += 1
            f = lambda p, w, x, kind=kind: mixer_half(kind, p, w, x)  # noqa: E731
            x = (jax.checkpoint(f) if c.remat else f)(p_mix, layers["norm1"][i], x)
            g = jax.checkpoint(expert_half, policy=keep_plan) if c.remat else expert_half
            x, aux = g(p_moe, layers["norm2"][i], x)
            lb = lb + aux["load_balance_loss"]
            loads.append(aux["expert_load"])
            dropped = dropped + aux["dropped"]
        aux = {"load_balance_loss": lb / len(c.layer_types),
               "expert_load": jnp.stack(loads), "dropped": dropped}
        return _norm(x, params["norm_f"], c.rms_eps), aux

    def hidden_states(self, params, tokens, key=None):
        return self.hidden_states_with_aux(params, tokens, key)[0]

    def unembed(self, params, x):
        return jnp.dot(x, params["head"]["weight"].T)

    def logits(self, params, tokens, key=None):
        return self.unembed(params, self.hidden_states(params, tokens, key))

    def loss_fn(self, params, tokens, targets, key=None, loss_mask=None,
                return_aux=False):
        """Mean next-token cross-entropy plus the load-balance term at
        ``aux_coeff``; ``return_aux=True`` also returns the aux dict (the
        load counters a training step hands back beside the loss)."""
        x, aux = self.hidden_states_with_aux(params, tokens, key)
        with monitor_spans.span("hybrid/unembed_xent"):
            losses = tp_lib.vocab_parallel_cross_entropy(
                self.unembed(params, x), targets, axis_name=None)
            loss = tp_lib.masked_mean(losses, loss_mask)
        loss = loss + self.config.aux_coeff * aux["load_balance_loss"]
        return (loss, aux) if return_aux else loss
