"""The plain reference of the ``bailing_hybrid`` decoder (Ling-3.0-flash): delta-
rule mixers whose decay is a vector a key channel (KDA) five to one with a
gated latent-attention (MLA) mixer, a leading dense SwiGLU layer and then
sparse experts routed by a group-limited sigmoid top-k under a selection bias,
beside one shared expert, in jax.numpy.

Written from the published configuration (the configuration's ``source``), the
Kimi Linear paper (arXiv:2510.26692) for the rule and the DeepSeek-V3 family's
modelling code for the group-limited choice; what has no key in the published
file is listed under the configuration file's ``assumed``. Float32, matmuls at
``highest`` precision, no kernels, no chunks, no cache. It imports nothing of
the program under test: weights come from :func:`make_weights` and the seed.

``rms(x; w) = w * x / sqrt(mean(x^2) + eps)`` (plain weight, at rest 1), no
bias anywhere, no embedding scale. ``n`` = 32 heads, ``d`` = 128.

* block — ``h <- h + mixer(rms(h; w1))``, ``h <- h + ffn(rms(h; w2))``; layer
  ``i`` of the published stack is latent where ``(i + 1) % layer_group_size ==
  0``, delta-rule else; dense below ``first_k_dense_replace``, experts else.
* delta-rule mixer, per token ``x``: ``q, k, v = x Wq, x Wk, x Wv`` (n x d
  each: as many key and value heads as query heads), each through a causal
  depthwise convolution of 4 taps and SiLU; q and k L2-normalised a head, q
  times ``d^-1/2``; v as it is. ``beta = sigmoid(x Wb)`` a head. The decay a
  key CHANNEL: ``a = x Wf + dt_bias`` (n x d), ``g = kda_lower_bound *
  sigmoid(exp(A_log) * a)`` with ``A_log`` a head, so every step's log decay
  lies in (-5, 0). State ``S`` (d x d) a head, token by token
  (:func:`kda_recurrence`, a ``lax.scan`` over positions): ``S <-
  Diag(exp(g_t)) S; u_t = beta_t (v_t - S^T k_t); S <- S + k_t u_t^T; o_t =
  S^T q_t``. Out: ``(rms_head(o_t; wn) * sigmoid(x Wg)) Wo``, the norm over
  each head's 128, the gate at full width.
* latent mixer: ``q = x Wq`` (n x (128 | 64)); ``[c | k_pe] = x Wkva`` (512 |
  64: one rotary key for all heads); ``[k_nope | v] = rms(c; kv_norm) Wkvb``
  (n x (128 | 128)); per-head rms of the assembled 192-wide q and k (``q_norm``,
  ``k_norm``, before rotary: a head's rotary key is the shared one under the
  head's own norm); rotary on the 64-wide parts (halves rotated against each
  other, theta 6e6, no scaling); causal softmax of ``(q . k) * 192^-1/2`` over
  materialised scores in row blocks; the context times ``sigmoid(x Wgate)``,
  one gate a head; ``Wo``.
* experts: ``s = sigmoid(m Wr)`` over the router's full width (512). Choice on
  ``s + b`` (``b`` the selection bias: state, no gradient): the experts are
  ``n_group`` groups of consecutive ones; a group's score is the sum of its two
  largest ``s + b``; the ``topk_group`` best groups stay; the top
  ``num_experts_per_tok`` of ``s + b`` inside them are chosen
  (``jax.lax.top_k``: the lowest index among equals). Weights: ``s`` at the
  chosen, over their sum, times ``routed_scaling_factor``. The experts **held
  here** (``held = (first, count)``) add ``w_e SwiGLU_e(m)`` for the tokens that
  chose them, the absent ones nothing; the shared expert adds one ungated
  SwiGLU. No balance loss: after every step ``b <- b + rate * sign(mean(n) -
  n)`` on the step's assignments (``afmoe_ref.bias_update``).
* head — ``rms(h; wf)``, an untied output matrix, mean next-token
  cross-entropy over the vocabulary held.

Departures from the published model: random seeded weights (the checkpoint
stores the rotary features interleaved: with random weights a relabelling of
columns), the chip's share of the experts and of the vocabulary, the layers
kept (``layers_kept``), no multi-token-prediction block (its published loss
weight is 0), documents packed into a row are not separated.

``precision="float8"`` is the control of the output check only: both operands
of every matmul rounded to e4m3, the rule's q, k, v among them. ``dims(...,
wrong=)`` names ONE deliberate departure for the tests that show each is
seen: ``scalar_decay`` (the decay's mean over a head's channels),
``unbounded_gate`` (``-exp(A_log) softplus(a)``), ``no_group_limit``,
``no_latent_gate``, ``no_qk_norm``, ``bf16_state`` (the rule's state rounded to
bfloat16 after every token).
"""

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference.afmoe_ref import (_by_token_blocks, _rms, _swiglu,  # noqa: F401
                                            bias_init, bias_update, shared_expert)
from benchmarks.reference.gpt_ref import _mm, _round, adam_init, seed_key  # noqa: F401
from benchmarks.reference.hybrid_ref import _l2, causal_conv_silu
from benchmarks.reference.mla_ref import _attention

KEYS = ("hidden_size", "num_hidden_layers", "first_k_dense_replace", "layer_group_size",
        "num_attention_heads", "head_dim", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "kv_lora_rank", "rope_theta", "short_conv_kernel_size",
        "kda_lower_bound", "intermediate_size", "moe_intermediate_size",
        "moe_shared_expert_intermediate_size", "num_experts", "num_experts_per_tok",
        "num_shared_experts", "n_group", "topk_group", "norm_topk_prob",
        "routed_scaling_factor", "rms_norm_eps", "vocab_size")
WRONG = ("", "scalar_decay", "unbounded_gate", "no_group_limit", "no_latent_gate", "no_qk_norm",
         "bf16_state")


def dims(config, wrong=""):
    """The sizes the reference needs, from a configuration file's keys.
    ``num_experts`` counts the experts held here; the router keeps its
    published width under ``router_num_experts`` (absent: all are held).
    ``layers_kept`` names the published layers that are here (absent: the
    first ``num_hidden_layers``): their kinds follow from their published
    numbers."""
    d = {k: config[k] for k in KEYS}
    if (config["score_function"], config["q_lora_rank"], config["rope_scaling"],
            config["kda_safe_gate"], config["num_kv_heads_for_linear_attn"]) != (
                "sigmoid", None, None, True, 0):
        raise ValueError("this reference scores by sigmoid, has no low-rank query path and no "
                         "rotary scaling, bounds its gate and gives every head its own key")
    if wrong not in WRONG:
        raise ValueError(f"wrong is one of {WRONG}")
    kept = config.get("layers_kept", list(range(d["num_hidden_layers"])))
    if len(kept) != d["num_hidden_layers"]:
        raise ValueError("layers_kept names num_hidden_layers layers")
    d["layer_types"] = tuple("latent" if (i + 1) % d["layer_group_size"] == 0 else "kda"
                             for i in kept)
    d["ffn_types"] = tuple("dense" if i < d["first_k_dense_replace"] else "moe" for i in kept)
    d["router_num_experts"] = config.get("router_num_experts", d["num_experts"])
    d["experts_held"] = (config.get("experts_held_first", 0), d["num_experts"])
    d["vocab_rows"] = config.get("padded_vocab_size", d["vocab_size"])
    d["shared_intermediate_size"] = (d["num_shared_experts"]
                                     * d["moe_shared_expert_intermediate_size"])
    d["load_balance_coeff"] = config["router_bias_update_rate"]
    d["kda_head_dim"] = d["head_dim"]          # under a name no attention view writes over
    d["wrong"] = wrong
    return d


def make_weights(d, key, dtype=jnp.float32):
    """Random weights from ``key`` (``seed_key(seed)``): normal(0, 0.02),
    residual projections scaled by 1/sqrt(2 L); norm weights 1 + normal(0.1)
    so that every one is exercised; the decay's rate ``exp(A_log) ~ U(0.5, 2)``
    a head and ``dt_bias ~ U(-3, 3)`` a channel, so that the per-step decays
    spread over (e^-5, 1)."""
    H, L = d["hidden_size"], d["num_hidden_layers"]
    Lk, Ll = d["layer_types"].count("kda"), d["layer_types"].count("latent")
    Lm, Ld = d["ffn_types"].count("moe"), d["ffn_types"].count("dense")
    nh, dh, dn, dr, dv, rank = (d["num_attention_heads"], d["head_dim"], d["qk_nope_head_dim"],
                                d["qk_rope_head_dim"], d["v_head_dim"], d["kv_lora_rank"])
    E, Eh = d["router_num_experts"], d["experts_held"][1]
    I, F, Fs, V = (d["intermediate_size"], d["moe_intermediate_size"],
                   d["shared_intermediate_size"], d["vocab_rows"])
    k = iter(jax.random.split(key, 48))

    def n(shape, std):
        return (std * jax.random.normal(next(k), shape, jnp.float32)).astype(dtype)

    def unit(shape):
        return (1 + n(shape, 0.1).astype(jnp.float32)).astype(dtype)

    def u(shape, lo, hi, dtype=jnp.float32):
        return jax.random.uniform(next(k), shape, jnp.float32, lo, hi).astype(dtype)

    res = 0.02 / (2 * L) ** 0.5
    hd = nh * dh
    return {
        "embed": n((V, H), 0.02), "head": n((V, H), 0.02), "norm_f": unit((H,)),
        "norm1": unit((L, H)), "norm2": unit((L, H)),
        "kda": {
            "w_q": n((Lk, H, hd), 0.02), "w_k": n((Lk, H, hd), 0.02), "w_v": n((Lk, H, hd), 0.02),
            "w_f": n((Lk, H, hd), 0.02), "w_g": n((Lk, H, hd), 0.02), "w_b": n((Lk, H, nh), 0.02),
            "conv_w": u((Lk, d["short_conv_kernel_size"], 3 * hd), -0.5, 0.5, dtype),
            "A_log": jnp.log(u((Lk, nh), 0.5, 2.0)), "dt_bias": u((Lk, hd), -3.0, 3.0),
            "norm_w": unit((Lk, dh)), "w_o": n((Lk, hd, H), res),
        },
        "attn": {
            "w_q": n((Ll, H, nh, dn + dr), 0.02), "w_kva": n((Ll, H, rank + dr), 0.02),
            "kv_norm": unit((Ll, rank)), "w_kvb": n((Ll, rank, nh, dn + dv), 0.02),
            "q_norm": unit((Ll, dn + dr)), "k_norm": unit((Ll, dn + dr)),
            "w_gate": n((Ll, H, nh), 0.02), "w_o": n((Ll, nh * dv, H), res),
        },
        "dense": {
            "w_gate": n((Ld, H, I), 0.02), "w_up": n((Ld, H, I), 0.02),
            "w_down": n((Ld, I, H), res),
        },
        "moe": {
            "router": n((Lm, H, E), 0.02),
            "w_gate": n((Lm, Eh, H, F), 0.02), "w_up": n((Lm, Eh, H, F), 0.02),
            "w_down": n((Lm, Eh, F, H), res),
            "shared_gate": n((Lm, H, Fs), 0.02), "shared_up": n((Lm, H, Fs), 0.02),
            "shared_down": n((Lm, Fs, H), res),
        },
    }


# --- the delta rule with a decay a key channel, token by token -----------------

def kda_recurrence(q, k, v, g, beta, time_block=32, bf16_state=False):
    """The recurrence of the module's docstring for one sequence. q, k (S, n,
    d) (already normalised, q scaled); v (S, n, d); g (S, n, d) the log decay
    of every key channel; beta (S, n). Returns o (S, n, d). Time is scanned in
    blocks that are recomputed in the backward pass, so that one state a block
    and not one a token is kept."""
    S, n, dk = q.shape
    dv = v.shape[-1]
    pad = -S % time_block
    if pad:
        z = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))  # noqa: E731
        q, k, v, g, beta = z(q), z(k), z(v), z(g), z(beta)

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[:, :, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * u[:, None, :]
        if bf16_state:
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    blocks = jax.tree.map(
        lambda a: a.reshape((a.shape[0] // time_block, time_block) + a.shape[1:]),
        (q, k, v, g, beta))
    _, o = jax.lax.scan(block, jnp.zeros((n, dk, dv), jnp.float32), blocks)
    return o.reshape(-1, n, dv)[:S]


def kda_mixer(lw, d, x, precision):
    """x (S, H), already normed -> (S, H)."""
    S = x.shape[0]
    n, dh, wrong = d["num_attention_heads"], d["head_dim"], d["wrong"]
    proj = lambda w: _mm("sh,hf->sf", x, w, precision)  # noqa: E731
    qkv = causal_conv_silu(jnp.concatenate([proj(lw["w_q"]), proj(lw["w_k"]), proj(lw["w_v"])],
                                           axis=-1), lw["conv_w"])
    q, k, v = (a.reshape(S, n, dh) for a in jnp.split(qkv, 3, axis=-1))
    beta = jax.nn.sigmoid(proj(lw["w_b"]))
    a = (proj(lw["w_f"]) + lw["dt_bias"]).reshape(S, n, dh)
    rate = jnp.exp(lw["A_log"])[:, None]
    g = d["kda_lower_bound"] * jax.nn.sigmoid(rate * a)
    if wrong == "unbounded_gate":
        g = -rate * jax.nn.softplus(a)
    if wrong == "scalar_decay":
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    o = kda_recurrence(_round(_l2(q) / dh ** 0.5, precision), _round(_l2(k), precision),
                       _round(v, precision), g, beta, bf16_state=wrong == "bf16_state")
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + d["rms_norm_eps"]) * lw["norm_w"]
    o = o * jax.nn.sigmoid(proj(lw["w_g"]).reshape(S, n, dh))
    return _mm("sf,fh->sh", o.reshape(S, n * dh), lw["w_o"], precision)


# --- gated latent attention -----------------------------------------------------

def rotary(x, theta):
    """x (S, heads, 64): halves rotated against each other by position."""
    dr = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def latent_mixer(lw, d, x, precision, q_block=256):
    """x (S, H), already normed -> (S, H)."""
    S = x.shape[0]
    nh, dn, rank, eps, wrong = (d["num_attention_heads"], d["qk_nope_head_dim"],
                                d["kv_lora_rank"], d["rms_norm_eps"], d["wrong"])
    q = _mm("sh,hnf->snf", x, lw["w_q"], precision)
    c = _mm("sh,hf->sf", x, lw["w_kva"], precision)
    kv = _mm("sr,rnf->snf", _rms(c[:, :rank], lw["kv_norm"], eps), lw["w_kvb"], precision)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(c[:, None, rank:], (S, nh, c.shape[1] - rank))], -1)
    if wrong != "no_qk_norm":
        q, k = _rms(q, lw["q_norm"], eps), _rms(k, lw["k_norm"], eps)
    q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], d["rope_theta"])], -1)
    k = jnp.concatenate([k[..., :dn], rotary(k[..., dn:], d["rope_theta"])], -1)
    ctx = _attention(q, k, kv[..., dn:], q.shape[-1] ** -0.5, q_block, precision)
    if wrong != "no_latent_gate":
        gate = jax.nn.sigmoid(_mm("sh,hn->sn", x, lw["w_gate"], precision))
        ctx = (ctx.reshape(S, nh, -1) * gate[..., None]).reshape(S, -1)
    return _mm("sf,fh->sh", ctx, lw["w_o"], precision)


# --- the expert layer -----------------------------------------------------------

def route(x, router, bias, d, precision):
    """(top-k expert ids (T, k), their weights (T, k), assignments to every
    expert of the router's width (E,)). The bias moves the choice — the
    groups' scores and the experts' — and not the weights."""
    E, k, G = d["router_num_experts"], d["num_experts_per_tok"], d["n_group"]
    s = jax.nn.sigmoid(_mm("th,he->te", x, router, precision))
    biased = s + jax.lax.stop_gradient(bias)
    if d["wrong"] != "no_group_limit":
        best = jnp.sum(jax.lax.top_k(biased.reshape(-1, G, E // G), 2)[0], axis=-1)    # (T, G)
        _, stay = jax.lax.top_k(best, d["topk_group"])
        kept = jnp.sum(jax.nn.one_hot(stay, G, dtype=jnp.float32), axis=1) > 0
        biased = jnp.where(jnp.repeat(kept, E // G, axis=1), biased, -jnp.inf)
    _, top_e = jax.lax.top_k(biased, k)
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    if d["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    counts = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0)
    return top_e, top_s * d["routed_scaling_factor"], counts


def expert_layer(lw, bias, d, x, precision, held=None):
    """x (T, H) -> (what the experts held add (T, H), assignments to every
    expert (E,)). The shared expert is ``afmoe_ref.shared_expert``'s."""
    first, count = d["experts_held"] if held is None else held
    top_e, top_w, counts = route(x, lw["router"], bias, d, precision)

    @jax.checkpoint
    def adds(e, wg, wu, wd):
        weight = jnp.sum(jnp.where(top_e == first + e, top_w, 0.0), axis=-1)
        return weight[:, None] * _swiglu(x, wg, wu, wd, precision)

    y, _ = jax.lax.scan(lambda acc, ew: (acc + adds(*ew), None), jnp.zeros_like(x),
                        (jnp.arange(count), lw["w_gate"], lw["w_up"], lw["w_down"]))
    return y, counts


# --- the model ------------------------------------------------------------------

def hidden(w, bias, d, tokens, *, precision="float32"):
    """Final hidden states (B, S, H) of a batch of token ids (B, S) and the
    assignments to every expert of the router's width, per expert layer
    (Lm, E)."""
    B, S = tokens.shape
    eps = d["rms_norm_eps"]
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    x = w["embed"][tokens]
    seen = {"kda": 0, "latent": 0, "moe": 0, "dense": 0}
    counts = []
    for i, (kind, ffn) in enumerate(zip(d["layer_types"], d["ffn_types"])):
        group, mixer = ("kda", kda_mixer) if kind == "kda" else ("attn", latent_mixer)
        lw = jax.tree.map(lambda a, j=seen[kind]: a[j], w[group])
        fw = jax.tree.map(lambda a, j=seen[ffn]: a[j], w[ffn])

        @jax.checkpoint
        def mix(x, lw, n1, mixer=mixer):
            # one row at a time, and in the backward pass one row's projections,
            # scores or scanned states: the map keeps each row's input alone
            one = jax.checkpoint(lambda r: mixer(lw, d, _rms(r, n1, eps), precision))
            return x + jax.lax.map(one, x)

        @jax.checkpoint
        def feed(x, fw, b, n2, ffn=ffn):
            m = _rms(x, n2, eps).reshape(B * S, -1)
            if ffn == "dense":
                y, n = _by_token_blocks(lambda m: _swiglu(
                    m, fw["w_gate"], fw["w_up"], fw["w_down"], precision), m), None
            else:
                y, n = expert_layer(fw, b, d, m, precision)
                y = y + shared_expert(fw, m, precision)
            return x + y.reshape(x.shape), n

        x = mix(x, lw, w["norm1"][i])
        b = bias[seen["moe"]] if ffn == "moe" else None
        x, n = feed(x, fw, b, w["norm2"][i])
        seen[kind] += 1
        seen[ffn] += 1
        if n is not None:
            counts.append(n)
    return _rms(x, w["norm_f"], eps), jnp.stack(counts)


def loss(w, bias, d, tokens, targets, *, precision="float32", token_block=2048):
    """Mean next-token cross-entropy over a batch (B, S), the logits
    ``token_block`` tokens at a time. Returns (loss, assignments (Lm, E))."""
    B, S = tokens.shape
    x, counts = hidden(w, bias, d, tokens, precision=precision)
    head = w["head"].astype(jnp.float32)
    block = min(token_block, B * S)

    @jax.checkpoint
    def some(args):
        xb, tgt = args
        lg = _mm("th,vh->tv", xb, head, precision)
        return jnp.sum(jax.nn.logsumexp(lg, -1)
                       - jnp.take_along_axis(lg, tgt[..., None], -1)[..., 0])

    total = jax.lax.map(some, (x.reshape(B * S // block, block, -1),
                               targets.reshape(B * S // block, block)))
    return jnp.sum(total) / (B * S), counts


def grad_step(w, bias, d, tokens, targets, *, precision="float32"):
    """(loss, assignments (Lm, E), gradients) of one batch: the first half of
    :func:`train_step`."""
    (value, counts), g = jax.value_and_grad(functools.partial(
        loss, precision=precision), has_aux=True)(w, bias, d, tokens, targets)
    return value, counts, g


def adam_update(w, opt, g, *, lr, b1=0.9, b2=0.999, eps=1e-8):
    """Plain Adam's step on the gradients ``g``, as ``gpt_ref.train_step``
    does it: the second half of :func:`train_step`. Returns (weights, state)."""
    t = opt["t"] + 1
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["m"], g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["v"], g)
    w = jax.tree.map(
        lambda p, m, v: p - lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps),
        w, m, v)
    return w, {"m": m, "v": v, "t": t}


def train_step(w, opt, bias, d, tokens, targets, *, lr, b1=0.9, b2=0.999, eps=1e-8,
               precision="float32"):
    """One step of plain Adam and the bias's own step: :func:`grad_step`, then
    :func:`adam_update`. Returns (weights, state, bias, loss, gradients,
    assignments (Lm, E)). At the cell's size the two halves are two programs
    (weights, both moments AND the gradients, 16 B a parameter, beside a
    row's activations are more than a chip holds): the adapter runs them in
    turn with the moments parked on the host in between — a schedule, the
    same mathematics."""
    value, counts, g = grad_step(w, bias, d, tokens, targets, precision=precision)
    w, opt = adam_update(w, opt, g, lr=lr, b1=b1, b2=b2, eps=eps)
    return w, opt, bias_update(bias, counts, d), value, g, counts
