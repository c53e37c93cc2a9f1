"""A decoder whose layers take their kind from a pattern: gated delta-rule
(linear-attention) mixers with a scalar decay a head or a decay a key channel,
state-space (Mamba-2) mixers, gated softmax-attention
mixers over all keys or over a sliding window, and latent-attention (MLA)
mixers, in any order, each followed by a dropless
sparse-expert layer with a shared expert, by a dense SwiGLU layer, or by
nothing (a block of one half).

Pre-norm residual blocks (``x += mixer(norm(x)); x += experts(norm(x))``)
with zero-centred RMSNorm (``x / rms(x) * (1 + w)``), no position table
(the attention layers carry partial rotary embeddings, the delta-rule layers
need none), no biases, an untied output head. ``layer_types`` names each
layer ``"linear"``, ``"kda"``, ``"ssm"``, ``"full"``, ``"window"`` or ``"latent"``, ``ffn_types``
its second half ``"moe"`` (the default everywhere), ``"dense"`` or ``"none"``
(no second half: ``norm2`` has a row for each layer that has one); parameters
of one kind are stacked on a leading axis under ``layers/gdn``, ``layers/kda``, ``layers/ssm``,
``layers/attn`` (both gated attention kinds, in the order they come),
``layers/mla``, ``layers/moe`` and ``layers/dense``; a kind no layer has has
no group. All linears are stored
(in, out). Switches for the blocks of other published decoders: plain
RMSNorm (``zero_centered_norm=False``: ``x / rms(x) * w``), a second norm on
each half's output before it is added (``sandwich_norms``:
``layers/norm1_post``, ``norm2_post``), the embedding scaled
(``embed_scale``), a sigmoid router with a selection bias, a shared
expert without its gate (``shared_gate=False``), ungated ``relu(x)^2`` experts
with one ``up`` matrix (``expert_activation="relu2"``: leaves ``w_up``,
``shared_up``), and an attention mixer without its gate (``attn_gate=False``),
its per-head norms (``qk_norm=False``) or rotary (``rotary_dim=0``), a latent
mixer with per-head q/k norms (``latent_qk_norm``) and a head-wise gate
(``latent_gate``), and a group-limited choice of the experts
(``router_groups``, ``router_groups_kept``).

* ``"linear"`` — fused ``q|k|v|z`` and ``b|a`` projections, causal depthwise
  convolution + SiLU on ``q|k|v``, :func:`ops.gated_delta_rule.gated_delta_rule`
  (float32 decay, write strength, norms and state whatever the policy),
  head-wise RMSNorm gated by ``silu(z)``, output projection.
* ``"kda"`` — the delta rule with a decay a key CHANNEL (Kimi Delta Attention):
  a fused ``q|k|v`` projection (``kda_heads`` of ``kda_head_dim`` each: as many
  key and value heads as query heads), causal depthwise convolution + SiLU on
  it, the decay projection ``w_f`` at full width (float32 out), ``g =
  kda_lower_bound * sigmoid(exp(A_log) * (x w_f + dt_bias))`` (``A_log`` a
  head, ``dt_bias`` a channel: every step's log decay in (``kda_lower_bound``,
  0), the bound :func:`ops.gated_delta_rule.kda_rule`'s kernels need), ``beta =
  sigmoid(x w_b)`` a head, the rule (float32 decay, write strength, norms and
  state whatever the policy), head-wise RMSNorm times ``sigmoid(x w_g)`` at
  full width, output projection. The aux dict gains ``kda_log_decay_min``.
* ``"ssm"`` — one fused projection ``xBC | z | dt`` (``ssm_heads x
  ssm_head_dim`` | 2 ``ssm_groups x ssm_state`` || the same inner width ||
  ``ssm_heads``; Mamba-2 publishes the columns as ``z | xBC | dt``: a
  relabelling that puts the convolved channels first, where the convolution
  kernel reads them in place), causal depthwise convolution + bias + SiLU on
  ``xBC``, ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``,
  :func:`ops.ssd.ssd_scan` (float32 decay and state whatever the policy; ``D``
  the skip), ``rmsnorm(y * silu(z)) * w`` over groups of ``inner /
  ssm_groups`` (gate before norm, a weight a channel), output projection.
* ``"full"`` — ``q|gate`` per head, per-head RMSNorm of q and k, rotary on
  the first ``rotary_dim`` features, causal flash attention in the (batch,
  seq, heads, head_dim) layout with grouped kv heads, ``sigmoid(gate)`` on
  the context, output projection.
* ``"window"`` — the same mixer, a query seeing its last ``window`` keys
  (``flash_attention(window=)``) and rotated over ``window_rotary_dim``
  features (``rotary_dim`` is the ``"full"`` layers'; 0 rotates nothing).
* ``"latent"`` — multi-head latent attention, by default without a gate or q/k norms:
  ``w_q`` (hidden, heads x ``qk_nope_dim`` | heads x ``qk_rope_dim``),
  ``w_kva`` (hidden, ``kv_lora_rank`` | ``qk_rope_dim``: the latent and ONE
  rotary key for all heads), ``kv_norm`` on the latent, ``w_kvb`` (latent,
  heads x ``qk_nope_dim`` | heads x ``v_head_dim``), rotary (``rope_scaling``:
  a published ``yarn`` entry) on the rotary features only, scores
  ``(q_nope . k_nope + q_pe . k_pe) * scale`` through
  ``flash_attention(second=)`` — the shared key is never repeated —, ``w_o``.
  ``latent_qk_norm``: a per-head RMSNorm (``q_norm``, ``k_norm`` over all
  ``qk_nope_dim + qk_rope_dim`` features) of the assembled query and key before
  rotary — the shared rotary key then leaves a head's own, its norm being the
  head's, and rides the kernel with as many heads as the query;
  ``latent_gate``: ``w_gate`` (hidden, heads), ``sigmoid`` of it on each head's
  context. Off (the defaults), the mixer is the one above, bit for bit.
* experts — :func:`transformer.moe.dropless_moe_layer` over the experts held
  here (``experts_held``), router at its full width. With
  ``router_score="sigmoid"`` the step may carry a selection bias, state that
  is no parameter: ``loss_fn(..., router_bias=b)`` routes with it, the aux
  dict returns the step's ``router_counts`` and
  :func:`transformer.moe.router_bias_update` moves it
  (:meth:`HybridDecoderModel.init_router_bias` starts it). ``router_groups`` >
  1 limits the choice to the ``router_groups_kept`` best groups of consecutive
  experts (:func:`transformer.moe.route_topk`'s ``groups``); the aux dict then
  gains ``router_group_hit`` (expert layers,): the share of tokens whose kept
  groups hold a group of the experts held here.

``loop_trips`` > 1 makes the stack a looped one: the same layers are walked
that many times on the same weights (:meth:`HybridDecoderModel.walk`), the
final norm closes every walk and its output opens the next, and every walk's
output leaves through the one head. ``params["exit_gate"]`` (a Linear(hidden,
1) with bias, shared by the walks) gives every token an exit distribution
``p^t = g^t prod_{j<t} (1 - g^j)``, the last walk taking what is left, and
``loss_fn`` is the mean over tokens of ``sum_t p^t l^t + exit_entropy_coeff
sum_t p^t log p^t`` (the gate, ``p`` and the weighting in float32, span
``hybrid/exit``; the exits' heads and losses under ``hybrid/unembed_xent``:
:func:`weighted_exit_losses`, which makes an exit's gradient where it makes
its loss, ``EXIT_BLOCK`` tokens at a time, so a block's logits are made once
a step, none stand between the passes and none beside another block's); the
aux dict gains ``exit_mass``, ``exit_losses`` (both (``loop_trips``,)) and
``exit_entropy``. With ``loop_trips`` 1 (the default) there is no gate and the
loss is the one cross-entropy it was.

``remat`` recomputes every block in the backward pass, a half at a time,
except what the half's policy keeps by name (``MIXER_SAVED``,
``EXPERTS_SAVED``): a mixer half keeps the results of its kernels — the
flash call's output and its log-sum-exp rows (``ops.attention.FLASH_SAVED``),
the delta rule's output and its chunks' entry states
(``ops.gated_delta_rule.RULE_SAVED``; ``KDA_SAVED`` for the per-channel rule), the
state-space scan's likewise
(``ops.ssd.SSD_SAVED``), which their backward rules read, so
no forward kernel runs twice — and the outputs of its input projections
(``"mix_proj"``: q, gate, k, v of an attention layer, ``q|k|v|z`` and ``b|a``
of a delta-rule layer, ``xBC|z|dt`` of a state-space layer; a ``"kda"`` layer's
five projections are named ``"kda_proj"`` and NOT kept: 1 GB a layer at 2 x
8,192 tokens, which five such layers beside 14 B a parameter of state do not
fit on a chip — they are computed again); an expert half keeps its routing plan. Norms, rotary, the convolution, the gates and
``w_o``'s operand are computed again. A looped stack holds ``loop_trips``
passes of activations for every layer of state, so under ``remat`` it keeps
the least that spares a kernel its second run (``LOOP_SAVED``): a block is
recomputed as one — its projections, norms, rotary and second half — from
its input, the routing plan and the flash call's results, and every walk's
closing norm from its input; which of the two rules holds follows from
``loop_trips``, not from a setting.

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
from jax.ad_checkpoint import checkpoint_name

from apex_tpu.monitor import spans as monitor_spans
from apex_tpu.ops.attention import FLASH_SAVED, flash_attention
from apex_tpu.ops.gated_delta_rule import (KDA_SAVED, RULE_SAVED, causal_conv_silu,
                                           gated_delta_rule, gated_rms_norm, kda_rule)
from apex_tpu.ops.rotary import apply_partial_rotary, yarn_mscale
from apex_tpu.ops.ssd import SSD_SAVED, ssd_scan
from apex_tpu.transformer import tensor_parallel as tp_lib
from apex_tpu.transformer.moe import (ACTIVATIONS, FIRST_LEAVES, dropless_moe_layer,
                                      silu_gate)

ATTENTION_KINDS = ("full", "window")
GROUP_OF_KIND = {"linear": "gdn", "full": "attn", "window": "attn", "latent": "mla",
                 "ssm": "ssm", "kda": "kda"}
MIXER_SCOPES = {"linear": "hybrid/gdn", "full": "hybrid/attn", "window": "hybrid/attn_win",
                "latent": "hybrid/attn_mla", "ssm": "hybrid/ssm", "kda": "hybrid/kda"}
# What ``remat`` keeps of a half beside its arguments, by name. A mixer half:
# the results of its kernels (the names their forward rules give them: which
# of them exist in a half follows from the kernels its layer kind runs) and
# the outputs of its input projections. An expert half: the routing plan
# (``transformer.moe``).
MIXER_SAVED = FLASH_SAVED + RULE_SAVED + KDA_SAVED + SSD_SAVED + ("mix_proj",)
EXPERTS_SAVED = ("moe_plan",)
# A looped stack (``loop_trips`` > 1) holds ``loop_trips`` passes of activations
# for every layer of state, so it keeps the least that spares a kernel its second
# run: of a block-pass its input, the routing plan and the flash call's results.
# The projections, norms, rotary and the second half are computed again.
LOOP_SAVED = EXPERTS_SAVED + FLASH_SAVED
# tokens whose logits a looped stack's exit makes, and turns into their gradient, at a time
EXIT_BLOCK = 8192


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
    # the attention mixer's output gate (``w_q`` holds q|gate a head) and its
    # per-head RMSNorms of q and k; rotary_dim 0 rotates nothing
    attn_gate: bool = True
    qk_norm: bool = True
    # "window" layers: keys a query sees, rotary features (None: rotary_dim)
    window: Optional[int] = None
    window_rotary_dim: Optional[int] = None
    # "latent" layers (num_heads of them): per-head and shared-rotary score
    # widths, the value head, the latent; a published ``rope_scaling`` entry
    # of type yarn (a mapping; kept as its sorted items) or None
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    rope_scaling: Any = None
    # per-head RMSNorm of the assembled (nope | rope) query and key before
    # rotary (leaves ``q_norm``, ``k_norm``); one sigmoid gate a head on the
    # context (leaf ``w_gate``). Off: the mixer above, bit for bit
    latent_qk_norm: bool = False
    latent_gate: bool = False
    # gated delta-rule layers
    linear_key_heads: int = 16
    linear_value_heads: int = 32
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    conv_kernel: int = 4
    # "kda" layers: as many key as value heads; the per-step log decay of a
    # key channel is ``kda_lower_bound * sigmoid(.)``, in (kda_lower_bound, 0)
    kda_heads: int = 32
    kda_head_dim: int = 128
    kda_lower_bound: float = -5.0
    # state-space (Mamba-2) layers: heads of ssm_head_dim, the state's rows,
    # groups that share B and C, tokens a chunk (their convolution has a bias)
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 8
    ssm_chunk: int = 128
    # experts: the router's width, which of them are held, experts a token
    router_experts: int = 512
    experts_held: Optional[Tuple[int, int]] = None      # (first, count); None = all
    top_k: int = 10
    expert_ffn: int = 512
    shared_ffn: int = 512
    normalize_topk: bool = True
    aux_coeff: float = 1e-3
    router_score: str = "softmax"
    route_scale: float = 1.0
    # group-limited choice: the router's experts are ``router_groups`` groups
    # of consecutive ones, of which a token keeps ``router_groups_kept`` (by
    # the sum of each group's two best biased scores) and chooses inside
    # them; 1 and 1: the choice over the whole row
    router_groups: int = 1
    router_groups_kept: int = 1
    shared_gate: bool = True
    # "silu_gate": SwiGLU experts over a fused gate|up; "relu2": ungated
    # relu(x W_up)^2 W_down (leaves w_up, shared_up)
    expert_activation: str = "silu_gate"
    # the balance term per sequence (mean over the rows) instead of batch-wise
    seq_aux: bool = False
    # second half of each layer, "moe" | "dense" | "none"; None: experts in every layer
    ffn_types: Optional[Tuple[str, ...]] = None
    dense_ffn: int = 0
    rms_eps: float = 1e-6
    zero_centered_norm: bool = True
    sandwich_norms: bool = False
    embed_scale: float = 1.0
    # a looped stack: the same layers walked ``loop_trips`` times on the same
    # weights, the final norm closing every walk and opening the next; every
    # walk's output leaves through the head, weighted by a learned exit
    # distribution whose entropy joins the loss at ``exit_entropy_coeff``
    loop_trips: int = 1
    exit_entropy_coeff: float = 0.0
    # recompute every block's two halves (mixer, experts) in the backward
    # pass, all but the results of their kernels, the mixers' input
    # projections and the routing plan (MIXER_SAVED, EXPERTS_SAVED)
    remat: bool = False
    attention_impl: str = "auto"
    # the recurrent mixers' kernels: the delta rule, the state-space scan and
    # the convolution and gated norm around either
    delta_impl: str = "auto"
    experts_impl: str = "auto"
    dtype: Any = jnp.float32

    def __post_init__(self):
        bad = set(self.layer_types) - set(GROUP_OF_KIND)
        if bad or not self.layer_types:
            raise ValueError("layer_types holds 'linear', 'kda', 'ssm', 'full', 'window' and "
                             f"'latent', got {self.layer_types!r}")
        if hasattr(self.rope_scaling, "items"):             # hashable, as the rest
            object.__setattr__(self, "rope_scaling", tuple(sorted(self.rope_scaling.items())))
        if "window" in self.layer_types and not self.window:
            raise ValueError("a 'window' layer needs window=")
        if set(self.ffn) - {"moe", "dense", "none"} or len(self.ffn) != len(self.layer_types):
            raise ValueError("ffn_types names every layer 'moe', 'dense' or 'none', "
                             f"got {self.ffn_types!r}")
        if self.expert_activation not in ACTIVATIONS:
            raise ValueError(f"expert_activation is one of {sorted(ACTIVATIONS)}, "
                             f"got {self.expert_activation!r}")
        if "ssm" in self.layer_types and self.ssm_heads % self.ssm_groups:
            raise ValueError("state-space heads must be a multiple of their groups")
        if (self.router_experts % self.router_groups
                or not 1 <= self.router_groups_kept <= self.router_groups):
            raise ValueError("router_groups divides the router's width and keeps 1 to all of "
                             f"them, got {self.router_groups} / {self.router_groups_kept}")
        if self.loop_trips < 1:
            raise ValueError(f"loop_trips counts the walks of the stack, got {self.loop_trips}")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"router_score is 'softmax' or 'sigmoid', got {self.router_score!r}")
        if self.num_heads % self.num_kv_heads or self.linear_value_heads % self.linear_key_heads:
            raise ValueError("query heads must be a multiple of kv heads, and value heads "
                             "of key heads")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.router_experts)

    @property
    def ffn(self) -> Tuple[str, ...]:
        return self.ffn_types or ("moe",) * len(self.layer_types)


def _exit_blocks(a):
    """``a`` (tokens, ...) cut into an exit's blocks of ``EXIT_BLOCK`` tokens;
    whole where that does not divide the tokens."""
    tokens = a.shape[0]
    return jnp.split(a, tokens // EXIT_BLOCK if tokens % EXIT_BLOCK == 0 else 1)


@jax.custom_vjp
def weighted_exit_losses(head, states, targets, weights):
    """A looped stack's exits through the one head: ``(sum over exits and
    tokens of weights^t l^t, l)`` with ``l^t`` the per-token cross-entropy of
    ``states[t] @ head.T`` (``head`` (vocabulary, hidden), ``states`` a tuple
    of (tokens, hidden), ``targets`` (tokens,), ``weights`` and ``l``
    (exits, tokens) float32). The cotangent of ``l`` is ``weights`` times one
    scalar, so an exit makes its gradient where it makes its loss:
    differentiated, the forward pass takes ``EXIT_BLOCK`` tokens at a time,
    makes their logits ONCE (in the states' dtype), from them the statistics
    of :func:`vocab_parallel_cross_entropy` and ``g = weights (softmax -
    onehot)`` in the logits' dtype, ``g @ head`` and ``g^T @ x`` — the latter
    summed over every block and exit in one float32 accumulator, rounded to
    the head's dtype once the last has added to it — and keeps those, not the
    logits; the backward rule multiplies them by the incoming scalar and runs
    no product. The gradient with respect to ``weights`` is ``l``; ``l``
    itself is handed out to be read and carries no gradient."""
    with monitor_spans.span("hybrid/unembed_xent"):
        losses = jnp.stack([jnp.concatenate([
            tp_lib.vocab_parallel_cross_entropy(jnp.dot(xb, head.T), tb, axis_name=None)
            for xb, tb in zip(_exit_blocks(x), _exit_blocks(targets))]) for x in states])
        return jnp.sum(weights * losses), losses


def _exits_fwd(head, states, targets, weights):
    d_head = jnp.zeros(head.shape, jnp.float32)
    losses, d_states = [], []
    with monitor_spans.span("hybrid/unembed_xent"):
        for x, w in zip(states, weights):
            rows, d_rows = [], []
            for xb, tb, wb in zip(_exit_blocks(x), _exit_blocks(targets), _exit_blocks(w)):
                # a block starts when the one before it has handed in its gradients:
                # the compiler would else make every block's logits first and hold them
                xb, d_head, d_rows = jax.lax.optimization_barrier((xb, d_head, d_rows))
                # the loss and wb (softmax - onehot) from the one set of logits
                loss, g = tp_lib.cross_entropy_with_grad(
                    jnp.dot(xb, head.T), tb, wb, axis_name=None)
                rows.append(loss)
                d_rows.append(jnp.dot(g, head))
                d_head = d_head + jax.lax.dot_general(
                    g, xb, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            losses.append(jnp.concatenate(rows))
            d_states.append(jnp.concatenate(d_rows))
        losses = jnp.stack(losses)
        return ((jnp.sum(weights * losses), losses),
                (d_head.astype(head.dtype), tuple(d_states), losses))


def _exits_bwd(res, cts):
    d_head, d_states, losses = res
    ct = cts[0]                                   # the losses' own is not read
    scaled = lambda d: (ct * d).astype(d.dtype)   # noqa: E731
    return scaled(d_head), tuple(scaled(d) for d in d_states), None, ct * losses


weighted_exit_losses.defvjp(_exits_fwd, _exits_bwd)


def _norm(x, w, eps, zero_centered=True):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    w = w.astype(jnp.float32)
    return (y * (1.0 + w if zero_centered else w)).astype(x.dtype)


class HybridDecoderModel:
    float32_params = ("A_log", "dt_bias", "D")

    def __init__(self, config: HybridDecoderConfig):
        self.config = config

    def _norm(self, x, w):
        c = self.config
        return _norm(x, w, c.rms_eps, c.zero_centered_norm)

    def init(self, key):
        """Random parameters (normal 0.02; residual projections scaled by
        1/sqrt(2 L ``loop_trips``); decay ``A ~ U(1, 16)``, ``dt ~ logU(1e-3, 1e-1)``)."""
        c = self.config
        H, L = c.hidden_size, len(c.layer_types)
        Lg, Ll, Ls, Lk = (c.layer_types.count(kind) for kind in ("linear", "latent", "ssm", "kda"))
        La = L - Lg - Ll - Ls - Lk
        Lm, Ld = c.ffn.count("moe"), c.ffn.count("dense")
        qk, vv = c.linear_key_heads * c.linear_key_dim, c.linear_value_heads * c.linear_value_dim
        keys = iter(jax.random.split(key, 32))
        n = lambda shape, std=0.02: (std * jax.random.normal(  # noqa: E731
            next(keys), shape, jnp.float32)).astype(c.dtype)
        res = 0.02 / (2 * L * c.loop_trips) ** 0.5    # a layer adds to the stream every walk
        dt = jnp.exp(jax.random.uniform(next(keys), (Lg, c.linear_value_heads), jnp.float32,
                                        jnp.log(1e-3), jnp.log(1e-1)))
        a = jax.random.uniform(next(keys), (Lg, c.linear_value_heads), jnp.float32, 1.0, 16.0)
        zeros = lambda shape: jnp.zeros(shape, c.dtype)  # noqa: E731
        # a norm's weight at rest: 0 where it is added to 1, 1 where it is not
        unit = zeros if c.zero_centered_norm else lambda shape: jnp.ones(shape, c.dtype)
        Eh = c.held[1]
        up, shared_up, gated = FIRST_LEAVES[c.expert_activation]
        layers = {
            "norm1": unit((L, H)), "norm2": unit((Lm + Ld, H)),
            "gdn": {
                "w_qkvz": n((Lg, H, 2 * qk + 2 * vv)), "w_ba": n((Lg, H, 2 * c.linear_value_heads)),
                "conv_w": jax.random.uniform(next(keys), (Lg, c.conv_kernel, 2 * qk + vv),
                                             jnp.float32, -0.5, 0.5).astype(c.dtype),
                "A_log": jnp.log(a), "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "norm_w": jnp.ones((Lg, c.linear_value_dim), c.dtype),
                "w_o": n((Lg, vv, H), res),
            },
            "attn": {
                "w_q": n((La, H, (2 if c.attn_gate else 1) * c.num_heads * c.head_dim)),
                "w_k": n((La, H, c.num_kv_heads * c.head_dim)),
                "w_v": n((La, H, c.num_kv_heads * c.head_dim)),
                "q_norm": unit((La, c.head_dim)), "k_norm": unit((La, c.head_dim)),
                "w_o": n((La, c.num_heads * c.head_dim, H), res),
            },
            "mla": {
                "w_q": n((Ll, H, c.num_heads * (c.qk_nope_dim + c.qk_rope_dim))),
                "w_kva": n((Ll, H, c.kv_lora_rank + c.qk_rope_dim)),
                "kv_norm": unit((Ll, c.kv_lora_rank)),
                "w_kvb": n((Ll, c.kv_lora_rank, c.num_heads * (c.qk_nope_dim + c.v_head_dim))),
                "w_o": n((Ll, c.num_heads * c.v_head_dim, H), res),
            },
            "moe": {
                "router": n((Lm, H, c.router_experts)),
                up: n((Lm, Eh, H, gated * c.expert_ffn)),
                "w_down": n((Lm, Eh, c.expert_ffn, H), res),
                shared_up: n((Lm, H, gated * c.shared_ffn)),
                "shared_down": n((Lm, c.shared_ffn, H), res),
                "shared_mix": n((Lm, H)),
            },
            "dense": {
                "w_gate_up": n((Ld, H, 2 * c.dense_ffn)),
                "w_down": n((Ld, c.dense_ffn, H), res),
            },
        }
        if not c.shared_gate:
            del layers["moe"]["shared_mix"]
        if not c.qk_norm:
            del layers["attn"]["q_norm"], layers["attn"]["k_norm"]
        if c.sandwich_norms:
            layers.update(norm1_post=unit((L, H)), norm2_post=unit((Lm + Ld, H)))
        for group, count in (("gdn", Lg), ("attn", La), ("mla", Ll), ("moe", Lm),
                             ("dense", Ld)):
            if not count:
                del layers[group]
        params = {
            "embedding": {"weight": n((c.vocab_size, H))},
            "head": {"weight": n((c.vocab_size, H))},
            "norm_f": unit((H,)),
            "layers": layers,
        }
        if Ls:                 # drawn last: the other groups keep the keys they had
            layers["ssm"] = self._init_ssm(Ls, n, keys, res)
        # what later kinds and switches add draws from a stream of its own
        keys = iter(jax.random.split(jax.random.fold_in(key, 1), 16))
        if Lk:
            layers["kda"] = self._init_kda(Lk, n, keys, res)
        if Ll and c.latent_qk_norm:
            layers["mla"].update(q_norm=unit((Ll, c.qk_nope_dim + c.qk_rope_dim)),
                                 k_norm=unit((Ll, c.qk_nope_dim + c.qk_rope_dim)))
        if Ll and c.latent_gate:
            layers["mla"]["w_gate"] = n((Ll, H, c.num_heads))
        if c.loop_trips > 1:   # one Linear(hidden, 1) with bias, shared by the walks
            params["exit_gate"] = {"weight": n((H, 1)), "bias": zeros((1,))}
        return params

    def _init_ssm(self, Ls, n, keys, res):
        """The state-space group (Mamba-2's start values: ``dt ~ logU(1e-3,
        1e-1)`` floored at 1e-4 through the inverse softplus, ``A ~ U(1, 16)``,
        ``D = 1``, the gated norm's weight 1)."""
        c = self.config
        H, nh = c.hidden_size, c.ssm_heads
        inner, bc = nh * c.ssm_head_dim, c.ssm_groups * c.ssm_state
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            next(keys), (Ls, nh), jnp.float32, jnp.log(1e-3), jnp.log(1e-1))), 1e-4)
        a = jax.random.uniform(next(keys), (Ls, nh), jnp.float32, 1.0, 16.0)
        return {
            "w_in": n((Ls, H, 2 * inner + 2 * bc + nh)),
            "conv_w": jax.random.uniform(next(keys), (Ls, c.conv_kernel, inner + 2 * bc),
                                         jnp.float32, -0.5, 0.5).astype(c.dtype),
            "conv_b": n((Ls, inner + 2 * bc)),
            "A_log": jnp.log(a), "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": jnp.ones((Ls, nh), jnp.float32),
            "norm_w": jnp.ones((Ls, inner), c.dtype),
            "w_o": n((Ls, inner, H), res),
        }

    def _init_kda(self, Lk, n, keys, res):
        """The per-channel delta-rule group: rates ``exp(A_log) ~ U(0.5, 2)`` a
        head and ``dt_bias ~ U(-3, 3)`` a channel, so that the per-step
        decays spread over (e^kda_lower_bound, 1) and not at one end."""
        c = self.config
        H, hd = c.hidden_size, c.kda_heads * c.kda_head_dim
        return {
            "w_qkv": n((Lk, H, 3 * hd)), "w_f": n((Lk, H, hd)), "w_g": n((Lk, H, hd)),
            "w_b": n((Lk, H, c.kda_heads)),
            "conv_w": jax.random.uniform(next(keys), (Lk, c.conv_kernel, 3 * hd),
                                         jnp.float32, -0.5, 0.5).astype(c.dtype),
            "A_log": jnp.log(jax.random.uniform(next(keys), (Lk, c.kda_heads), jnp.float32,
                                                0.5, 2.0)),
            "dt_bias": jax.random.uniform(next(keys), (Lk, hd), jnp.float32, -3.0, 3.0),
            "norm_w": jnp.ones((Lk, c.kda_head_dim), c.dtype),
            "w_o": n((Lk, hd, H), res),
        }

    def init_router_bias(self):
        """The selection bias of every expert layer's router at rest: state
        of a training step, beside its parameters and not among them."""
        c = self.config
        return jnp.zeros((c.ffn.count("moe"), c.router_experts), jnp.float32)

    # --- mixers ---------------------------------------------------------------

    def _delta_mixer(self, p, x):
        c = self.config
        b, s, _ = x.shape
        hk, hv, dk, dv = (c.linear_key_heads, c.linear_value_heads,
                          c.linear_key_dim, c.linear_value_dim)
        with monitor_spans.span("mix/proj_in"):
            qkvz = checkpoint_name(jnp.dot(x, p["w_qkvz"]), "mix_proj")
            ba = checkpoint_name(jnp.dot(x, p["w_ba"], preferred_element_type=jnp.float32),
                                 "mix_proj")
        # q|k|v and z are read where the projection left them: no slice of qkvz
        q, k, v = causal_conv_silu(qkvz, p["conv_w"], widths=(hk * dk, hk * dk, hv * dv),
                                   impl=c.delta_impl)
        with monitor_spans.span("mix/place"):
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = (-jnp.exp(p["A_log"].astype(jnp.float32))
                 * jax.nn.softplus(ba[..., hv:] + p["dt_bias"].astype(jnp.float32)))
        o = gated_delta_rule(q.reshape(b, s, hk, dk), k.reshape(b, s, hk, dk),
                             v.reshape(b, s, hv, dv), g, beta, impl=c.delta_impl)
        o = gated_rms_norm(o, qkvz, p["norm_w"], c.rms_eps, impl=c.delta_impl)
        with monitor_spans.span("mix/proj_out"):
            return jnp.dot(o.reshape(b, s, hv * dv), p["w_o"])

    def _kda_mixer(self, p, x):
        """(what the mixer adds, the smallest per-step log decay it took)."""
        c = self.config
        b, s, _ = x.shape
        h, d = c.kda_heads, c.kda_head_dim
        f32 = jnp.float32
        with monitor_spans.span("mix/proj_in"):
            # named, and not among MIXER_SAVED: five such layers' projections (1 GB a
            # layer at 2 x 8,192 tokens) beside 14 B a parameter of state do not fit a chip
            qkv = checkpoint_name(jnp.dot(x, p["w_qkv"]), "kda_proj")
            a = checkpoint_name(jnp.dot(x, p["w_f"], preferred_element_type=f32), "kda_proj")
            gate = checkpoint_name(jnp.dot(x, p["w_g"]), "kda_proj")
            b_logit = checkpoint_name(jnp.dot(x, p["w_b"], preferred_element_type=f32),
                                      "kda_proj")
        # q|k|v are read where the projection left them: no slice of qkv
        q, k, v = causal_conv_silu(qkv, p["conv_w"], widths=(h * d,) * 3, impl=c.delta_impl)
        with monitor_spans.span("mix/place"):
            beta = jax.nn.sigmoid(b_logit)
            rate = jnp.exp(p["A_log"].astype(f32))[:, None]
            g = c.kda_lower_bound * jax.nn.sigmoid(
                rate * (a + p["dt_bias"].astype(f32)).reshape(b, s, h, d))
        heads = lambda z: z.reshape(b, s, h, d)  # noqa: E731
        o = kda_rule(heads(q), heads(k), heads(v), g, beta, impl=c.delta_impl)
        with monitor_spans.span("mix/place"):
            o = o.astype(f32)
            y = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + c.rms_eps)
            y = (y * p["norm_w"].astype(f32) * jax.nn.sigmoid(heads(gate).astype(f32))
                 ).astype(x.dtype)
        with monitor_spans.span("mix/proj_out"):
            return jnp.dot(y.reshape(b, s, h * d), p["w_o"]), jnp.min(g)

    def _ssm_mixer(self, p, x):
        c = self.config
        b, s, _ = x.shape
        nh, dh, groups, state = c.ssm_heads, c.ssm_head_dim, c.ssm_groups, c.ssm_state
        inner, bc = nh * dh, groups * state
        with monitor_spans.span("mix/proj_in"):
            proj = checkpoint_name(jnp.dot(x, p["w_in"]), "mix_proj")
        # xBC, z and dt are read where the projection left them
        xs, B, C = causal_conv_silu(proj, p["conv_w"], p["conv_b"],
                                    widths=(inner, bc, bc), impl=c.delta_impl)
        with monitor_spans.span("mix/place"):
            dt = jax.nn.softplus(proj[..., 2 * inner + 2 * bc:].astype(jnp.float32)
                                 + p["dt_bias"].astype(jnp.float32))
            A = -jnp.exp(p["A_log"].astype(jnp.float32))
        y = ssd_scan(xs.reshape(b, s, nh, dh), dt, A, B.reshape(b, s, groups, state),
                     C.reshape(b, s, groups, state), p["D"], chunk=c.ssm_chunk,
                     impl=c.delta_impl)
        y = gated_rms_norm(y.reshape(b, s, groups, inner // groups), proj,
                           p["norm_w"].reshape(groups, inner // groups), c.rms_eps,
                           gate_first=True, gate_start=inner + 2 * bc, impl=c.delta_impl)
        with monitor_spans.span("mix/proj_out"):
            return jnp.dot(y.reshape(b, s, inner), p["w_o"])

    def _attention_mixer(self, p, x, kind="full"):
        c = self.config
        b, s, _ = x.shape
        nh, nkv, dh = c.num_heads, c.num_kv_heads, c.head_dim
        banded = kind == "window"
        rot = c.rotary_dim if not banded or c.window_rotary_dim is None else c.window_rotary_dim
        with monitor_spans.span("mix/proj_in"):
            kv = lambda w: jnp.dot(x, w).reshape(b, s, nkv, dh)  # noqa: E731
            if c.attn_gate:
                qg = jnp.dot(x, p["w_q"]).reshape(b, s, nh, 2 * dh)
                q, gate, k, v = (checkpoint_name(a, "mix_proj") for a in (
                    qg[..., :dh], qg[..., dh:], kv(p["w_k"]), kv(p["w_v"])))
            else:
                q, k, v = (checkpoint_name(a, "mix_proj") for a in (
                    jnp.dot(x, p["w_q"]).reshape(b, s, nh, dh), kv(p["w_k"]), kv(p["w_v"])))

        def placed(x, w):                          # per-head norm, then its position
            x = self._norm(x, w) if c.qk_norm else x
            return apply_partial_rotary(x, rot, c.rope_theta) if rot else x

        if c.qk_norm or rot:
            with monitor_spans.span("mix/place"):
                q, k = placed(q, p.get("q_norm")), placed(k, p.get("k_norm"))
        ctx = flash_attention(q, k, v, causal=True, scale=dh ** -0.5, layout="bshd",
                              impl=c.attention_impl, window=c.window if banded else None)
        if c.attn_gate:
            with monitor_spans.span("mix/place"):
                ctx = ctx * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(ctx.dtype)
        with monitor_spans.span("mix/proj_out"):
            return jnp.dot(ctx.reshape(b, s, nh * dh), p["w_o"])

    def _latent_mixer(self, p, x):
        c = self.config
        b, s, _ = x.shape
        nh, dn, dr, dv, rank = (c.num_heads, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim,
                                c.kv_lora_rank)
        # the weights are cut, not the activations: each product lands where
        # the kernel reads it (columns stored nope | rope and key | value);
        # ``mla/down`` and ``mla/up`` are this mixer's ``mix/proj_in``
        with monitor_spans.span("mla/down"):
            q_nope = jnp.dot(x, p["w_q"][:, :nh * dn]).reshape(b, s, nh, dn)
            q_pe = jnp.dot(x, p["w_q"][:, nh * dn:]).reshape(b, s, nh, dr)
            latent = self._norm(jnp.dot(x, p["w_kva"][:, :rank]), p["kv_norm"])
            k_pe = jnp.dot(x, p["w_kva"][:, rank:]).reshape(b, s, 1, dr)
            if c.latent_gate:
                gate = jnp.dot(x, p["w_gate"], preferred_element_type=jnp.float32)
        with monitor_spans.span("mla/up"):
            k_nope = jnp.dot(latent, p["w_kvb"][:, :nh * dn]).reshape(b, s, nh, dn)
            v = jnp.dot(latent, p["w_kvb"][:, nh * dn:]).reshape(b, s, nh, dv)
        scale = (dn + dr) ** -0.5
        if c.rope_scaling is not None:         # yarn's temperature, on the scores
            entry = dict(c.rope_scaling)
            scale *= yarn_mscale(entry["factor"], entry.get("mscale_all_dim", 0.0)) ** 2
        with monitor_spans.span("mix/place"):
            if c.latent_qk_norm:
                (q_nope, q_pe), (k_nope, k_pe) = (
                    self._assembled_norm(nope, pe, w)
                    for nope, pe, w in ((q_nope, q_pe, p["q_norm"]), (k_nope, k_pe, p["k_norm"])))
            q_pe, k_pe = (apply_partial_rotary(a, dr, c.rope_theta, scaling=c.rope_scaling)
                          for a in (q_pe, k_pe))
        ctx = flash_attention(q_nope, k_nope, v, causal=True, scale=scale, layout="bshd",
                              impl=c.attention_impl, second=(q_pe, k_pe))
        if c.latent_gate:
            with monitor_spans.span("mix/place"):
                ctx = ctx * jax.nn.sigmoid(gate)[..., None].astype(ctx.dtype)
        with monitor_spans.span("mix/proj_out"):
            return jnp.dot(ctx.reshape(b, s, nh * dv), p["w_o"])

    def _assembled_norm(self, nope, pe, w):
        """The RMSNorm of a latent head's assembled ``nope | pe`` features
        (``w`` over all of them), returned apart as they came: ``nope`` (b, s,
        h, dn); ``pe`` (b, s, h or 1, dr) — a rotary key shared by the heads
        leaves a head's own, its norm being the head's."""
        c = self.config
        dn, f32 = nope.shape[-1], jnp.float32
        n32, p32, w = nope.astype(f32), pe.astype(f32), w.astype(f32)
        w = 1.0 + w if c.zero_centered_norm else w
        r = jax.lax.rsqrt((jnp.sum(n32 * n32, -1, keepdims=True)
                           + jnp.sum(p32 * p32, -1, keepdims=True)) / (dn + pe.shape[-1])
                          + c.rms_eps)
        return (n32 * r * w[:dn]).astype(nope.dtype), (p32 * r * w[dn:]).astype(pe.dtype)

    def _experts(self, p, x, router_bias=None):
        c = self.config
        return dropless_moe_layer(
            p, x, top_k=c.top_k, experts_held=c.held, normalize_weights=c.normalize_topk,
            impl=c.experts_impl, score=c.router_score, route_scale=c.route_scale,
            router_bias=router_bias, shared_gate=c.shared_gate,
            sequence_balance=c.seq_aux, activation=c.expert_activation,
            groups=c.router_groups, groups_kept=c.router_groups_kept)

    @staticmethod
    def _dense(p, x):
        return jnp.dot(silu_gate(jnp.dot(x, p["w_gate_up"])), p["w_down"])

    # --- the halves of a block ------------------------------------------------

    def _added(self, y, post):
        """What a half adds to the stream: its output, normed again where
        the block is a sandwich."""
        return y if post is None else self._norm(y, post)

    def _mixer_half(self, kind):
        """``(p, w, post, x) -> x + mixer(norm(x))`` of a ``kind`` layer."""
        mixers = {"linear": self._delta_mixer, "latent": self._latent_mixer,
                  "ssm": self._ssm_mixer}

        def half(p, w, post, x):
            with monitor_spans.span(MIXER_SCOPES[kind]):
                h = self._norm(x, w)
                if kind == "kda":          # hands its smallest log decay out beside the stream
                    y, low = self._kda_mixer(p, h)
                    return x + self._added(y, post), low
                y = mixers[kind](p, h) if kind in mixers else self._attention_mixer(p, h, kind)
                return x + self._added(y, post)

        return self._recomputed(half, MIXER_SAVED)

    def _expert_half(self, p, w, post, bias, x):
        with monitor_spans.span("hybrid/moe"):
            y, aux = self._experts(p, self._norm(x, w), bias)
            return x + self._added(y, post), aux

    def _dense_half(self, p, w, post, x):
        with monitor_spans.span("hybrid/dense"):
            return x + self._added(self._dense(p, self._norm(x, w)), post)

    def _recomputed(self, f, saved=(), block=False):
        """A half of a block (or, ``block``, a whole block or a walk's closing
        norm) as the stack runs it. Under ``remat`` the backward pass holds
        its arguments and the values named in ``saved`` and computes the rest
        of it again: a stack walked once a half at a time, a looped stack a
        block at a time, so of the two the other is returned as it is."""
        if not self.config.remat or block != (self.config.loop_trips > 1):
            return f
        return jax.checkpoint(
            f, policy=jax.checkpoint_policies.save_only_these_names(*saved))

    # --- the stack ------------------------------------------------------------

    def walk(self, params, x, router_bias=None):
        """One walk of the stack from the stream ``x`` through the final norm:
        (the normed output, aux): ``load_balance_loss`` (mean over the expert
        layers), ``expert_load`` (expert layers, held) and ``router_counts``
        (expert layers, router width) int32, ``dropped`` (); with a group-limited
        router ``router_group_hit`` (expert layers,), with ``"kda"`` layers
        ``kda_log_decay_min`` (). ``router_bias``
        (expert layers, router width): the routers' selection bias, where the
        step carries one. Under ``remat`` each half of a block is recomputed
        by itself (``MIXER_SAVED``, ``EXPERTS_SAVED``); in a looped stack the
        block is recomputed as one, from its input and its flash call's
        results (``LOOP_SAVED``), and the final norm from its input."""
        c = self.config
        layers = params["layers"]
        expert_half = self._recomputed(self._expert_half, EXPERTS_SAVED)
        dense_half = self._recomputed(self._dense_half)
        post1, post2 = layers.get("norm1_post"), layers.get("norm2_post")
        seen = {"gdn": 0, "ssm": 0, "attn": 0, "mla": 0, "kda": 0, "moe": 0, "dense": 0}
        lb, loads, counts, dropped, hits, lows = 0.0, [], [], 0, [], []

        def take(group):
            j = seen[group]
            seen[group] += 1
            return j, jax.tree.map(lambda a: a[j], layers[group])

        second = 0             # second halves so far: the row of norm2 (and norm2_post)
        for i, (kind, ffn) in enumerate(zip(c.layer_types, c.ffn)):
            _, p_mix = take(GROUP_OF_KIND[kind])
            j, p_ffn = take(ffn) if ffn != "none" else (None, None)

            def block(p_mix, p_ffn, x):            # layer i, run before the loop moves on
                x = self._mixer_half(kind)(p_mix, layers["norm1"][i],
                                           None if post1 is None else post1[i], x)
                x, low = x if kind == "kda" else (x, None)
                if ffn == "none":
                    return x, None, low
                post = None if post2 is None else post2[second]
                if ffn == "dense":
                    return dense_half(p_ffn, layers["norm2"][second], post, x), None, low
                bias = None if router_bias is None else router_bias[j]
                return (*expert_half(p_ffn, layers["norm2"][second], post, bias, x), low)

            x, aux, low = self._recomputed(block, LOOP_SAVED, block=True)(p_mix, p_ffn, x)
            second += ffn != "none"
            if low is not None:
                lows.append(low)
            if aux is None:
                continue
            if "router_group_hit" in aux:
                hits.append(aux["router_group_hit"])
            lb = lb + aux["load_balance_loss"]
            loads.append(aux["expert_load"])
            counts.append(aux["router_counts"])
            dropped = dropped + aux["dropped"]
        stacked = lambda rows, width: (jnp.stack(rows) if rows  # noqa: E731
                                       else jnp.zeros((0, width), jnp.int32))
        aux = {"load_balance_loss": lb / max(len(loads), 1),
               "expert_load": stacked(loads, c.held[1]),
               "router_counts": stacked(counts, c.router_experts), "dropped": dropped}
        if hits:               # a group-limited router's: the share of tokens a layer whose
            aux["router_group_hit"] = jnp.stack(hits)      # kept groups hold the held experts'
        if lows:               # the smallest per-step log decay the "kda" layers took
            aux["kda_log_decay_min"] = jnp.min(jnp.stack(lows))
        return self._recomputed(self._norm, block=True)(x, params["norm_f"]), aux

    def trip_states(self, params, tokens, router_bias=None):
        """(the normed output of each of the ``loop_trips`` walks, the output
        of one opening the next: one entry where the stack is walked once;
        :meth:`walk`'s aux, the balance term its mean over the walks and the
        counters their sum)."""
        c = self.config
        with monitor_spans.span("hybrid/embed"):
            x = params["embedding"]["weight"][tokens]
            if c.embed_scale != 1.0:
                x = x * jnp.asarray(c.embed_scale, x.dtype)
        states, auxes = [], []
        for _ in range(c.loop_trips):
            x, aux = self.walk(params, x, router_bias)
            states.append(x)
            auxes.append(aux)
        if len(auxes) > 1:
            aux = jax.tree.map(lambda *a: sum(a), *auxes)
            aux["load_balance_loss"] = aux["load_balance_loss"] / c.loop_trips
        return states, aux

    def hidden_states_with_aux(self, params, tokens, key=None, router_bias=None):
        """(final hidden states, aux): the last of :meth:`trip_states`."""
        del key                                    # no dropout in this block
        states, aux = self.trip_states(params, tokens, router_bias)
        return states[-1], aux

    def hidden_states(self, params, tokens, key=None):
        return self.hidden_states_with_aux(params, tokens, key)[0]

    def unembed(self, params, x):
        return jnp.dot(x, params["head"]["weight"].T)

    def logits(self, params, tokens, key=None):
        return self.unembed(params, self.hidden_states(params, tokens, key))

    def loss_fn(self, params, tokens, targets, key=None, loss_mask=None,
                return_aux=False, router_bias=None):
        """Mean next-token cross-entropy plus the load-balance term at
        ``aux_coeff``; ``return_aux=True`` also returns the aux dict (the
        load counters a training step hands back beside the loss, and the
        ``router_counts`` that move a selection bias). A looped stack's loss
        is :meth:`_exit_loss` over every walk's output."""
        del key                                    # no dropout in this block
        states, aux = self.trip_states(params, tokens, router_bias)
        if len(states) > 1:
            loss, exits = self._exit_loss(params, states, targets, loss_mask)
            aux = dict(aux, **exits)
        else:
            with monitor_spans.span("hybrid/unembed_xent"):
                losses = tp_lib.vocab_parallel_cross_entropy(
                    self.unembed(params, states[0]), targets, axis_name=None)
                loss = tp_lib.masked_mean(losses, loss_mask)
        loss = loss + self.config.aux_coeff * aux["load_balance_loss"]
        return (loss, aux) if return_aux else loss

    @staticmethod
    def exit_log_probs(gate, states):
        """``log p^t`` (trips, ...) per token, float32, from the walks' outputs:
        ``g^t = sigmoid(h^t . w + b)`` with the one ``gate`` (``weight``
        (hidden, 1), ``bias`` (1,)), ``p^t = g^t prod_{j<t} (1 - g^j)``, the
        last walk taking what is left (its own gate is never asked):
        ``log p^t = log g^t + sum_{j<t} log (1 - g^j)``, by the log-sigmoids."""
        z = jnp.stack([jnp.dot(x, gate["weight"], preferred_element_type=jnp.float32)[..., 0]
                       for x in states[:-1]]) + gate["bias"].astype(jnp.float32)
        stays = jnp.cumsum(jax.nn.log_sigmoid(-z), axis=0)
        return jnp.concatenate([jax.nn.log_sigmoid(z[:1]),
                                jax.nn.log_sigmoid(z[1:]) + stays[:-1], stays[-1:]])

    def _exit_loss(self, params, states, targets, loss_mask=None):
        """The looped stack's objective over the walks' outputs ``h^t``: every
        one leaves through the one head (``l^t``, per token),
        :meth:`exit_log_probs` gives the exit distribution ``p``, and the loss
        is the mean over tokens of ``sum_t p^t l^t + exit_entropy_coeff sum_t
        p^t log p^t``: the expected loss less the coefficient times the
        distribution's entropy. The gate, ``p`` and the weighting in float32;
        gradients flow through ``p``, which the walks' outputs give before any
        exit is taken: ``p^t`` times a token's share of the mean is the
        cotangent of ``l^t`` up to the loss's own, and
        :func:`weighted_exit_losses` takes it as its weights. Returns (loss,
        {``exit_mass`` (trips,) the mean of ``p^t``, ``exit_losses`` (trips,)
        the mean of ``l^t``, read without a gradient, ``exit_entropy`` ()})."""
        tokens = targets.size
        mean = lambda a: tp_lib.masked_mean(a, loss_mask)  # noqa: E731
        # the gate and the exits read the walks' outputs where they stand: the compiler
        # would else make them again, inside the gate's backward, from every half's output
        # of every block-pass before them, and hold those until then
        states = jax.lax.optimization_barrier(tuple(states))
        with monitor_spans.span("hybrid/exit"):
            log_p = self.exit_log_probs(params["exit_gate"], states)
            p = jnp.exp(log_p)
            plogp = jnp.sum(p * log_p, axis=0)
            # a token's share of the mean: the cotangent of every l^t is p^t times it
            counted = (jnp.ones(targets.shape, jnp.float32) if loss_mask is None
                       else loss_mask.astype(jnp.float32))
            share = counted / jnp.maximum(jnp.sum(counted), 1.0)
        expected, losses = weighted_exit_losses(
            params["head"]["weight"], tuple(x.reshape(tokens, -1) for x in states),
            targets.reshape(tokens), (p * share).reshape(len(states), tokens))
        losses = jax.lax.stop_gradient(losses).reshape(p.shape)
        with monitor_spans.span("hybrid/exit"):
            aux = {"exit_mass": jnp.stack([mean(a) for a in p]),
                   "exit_losses": jnp.stack([mean(a) for a in losses]),
                   "exit_entropy": -mean(plogp)}
            return expected + self.config.exit_entropy_coeff * mean(plogp), aux
