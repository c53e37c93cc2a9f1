"""The plain reference of the ``deepseek_v2`` decoder (DeepSeek-V2-Lite):
multi-head latent attention (MLA) with a low-rank key/value path and one
rotary key shared by all heads, a leading dense SwiGLU layer and then sparse
experts routed by a softmax top-k beside two shared experts, in jax.numpy.

Written from the published configuration (the configuration's ``source``) and
the family's modelling code; what has no key there is listed under the
configuration file's ``assumed``. Float32, matmuls at ``highest`` precision,
no kernels, no cache. It imports nothing of the program under test: weights
come from :func:`make_weights` and the seed.

``rms(x; w) = w * x / sqrt(mean(x^2) + eps)`` (plain weight, at rest 1), no
bias anywhere, no embedding scale, no q/k norms, no gate on attention.

* block — ``h <- h + attn(rms(h; w1))``, ``h <- h + ffn(rms(h; w2))``.
* attention, per token ``x``: ``q = x Wq`` (heads x 192), split per head
  ``q_nope`` (128) | ``q_pe`` (64); ``c = x Wkva`` (576), split ``c_kv``
  (512) | ``k_pe`` (64: ONE key for all heads); ``kv = rms(c_kv; wkvn) Wkvb``
  (heads x 256), split per head ``k_nope`` (128) | ``v`` (128). Rotary on
  ``q_pe`` and ``k_pe`` only (halves rotated against each other), at yarn's
  frequencies over the 32 pairs: ``f_i = theta^(-2i/64)``, ``ramp_i =
  clip((i - low) / (high - low), 0, 1)`` with ``low`` = floor and ``high`` =
  ceil of ``64 ln(orig / (2 pi r)) / (2 ln theta)`` at ``r = beta_fast`` and
  ``beta_slow`` (10 and 23 here), frequency ``f_i (1 - ramp_i) + f_i / factor
  ramp_i``; cos and sin times ``mscale(factor, mscale) / mscale(factor,
  mscale_all_dim)`` (1 here), ``mscale(s, m) = 0.1 m ln s + 1``. Scores
  ``(q_nope . k_nope + q_pe . k_pe) * 192^-0.5 * mscale(factor,
  mscale_all_dim)^2``, causal, float32 softmax over materialised scores in
  row blocks; ``o = P v`` (heads x 128) ``Wo``. ``q_lora_rank`` is null: no
  low-rank query path.
* feed-forward — a dense layer (the first ``first_k_dense_replace``):
  ``(silu(m Wgate) * m Wup) Wdown`` at ``intermediate_size``. An expert
  layer: ``s = softmax(m Wr)`` over the router's full width, greedy
  ``top_k``, weights the chosen ``s`` as they are (``norm_topk_prob`` false,
  ``routed_scaling_factor`` 1); the experts **held here** (``held = (first,
  count)`` of the router's width) add ``s_e Wd(silu(Wg m) * Wu m)`` for the
  tokens that chose them, the absent ones add nothing; the
  ``n_shared_experts`` shared experts add one ungated SwiGLU of their joint
  width.
* balance term, per sequence (``seq_aux``): for each row of S tokens ``f_i =
  E n_i / (k S)`` (``n_i`` the row's assignments to expert i) and ``P_i`` the
  row's mean ``s_i``; each expert layer adds ``aux_loss_alpha * mean_rows
  sum_i f_i P_i`` to the loss, over the router's full width.
* head — ``rms(h; wf)``, an untied output matrix, mean next-token
  cross-entropy over the vocabulary held.

Departures from the published model: random seeded weights (the checkpoint
stores the rotary features interleaved and permutes them to halves: with
random weights a relabelling of columns), the chip's share of the experts
and of the vocabulary, the layers kept (``layers_kept``), documents packed
into a row are not separated.

``precision="float8"`` is the control of the output check only: both operands
of every matmul (attention's q, k, v among them) rounded to e4m3.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.afmoe_ref import (_by_token_blocks, _rms, _swiglu,  # noqa: F401
                                            shared_expert)
from benchmarks.reference.gpt_ref import _mm, adam_init, seed_key  # noqa: F401

KEYS = ("hidden_size", "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "rope_theta",
        "rope_scaling", "intermediate_size", "moe_intermediate_size", "n_routed_experts",
        "num_experts_per_tok", "n_shared_experts", "norm_topk_prob", "routed_scaling_factor",
        "scoring_func", "seq_aux", "aux_loss_alpha", "rms_norm_eps", "vocab_size")


def dims(config):
    """The sizes the reference needs, from a configuration file's keys.
    ``n_routed_experts`` counts the experts held here; the router keeps its
    published width under ``router_num_experts`` (absent: all are held).
    ``layers_kept`` names the published layers that are here (absent: the
    first ``num_hidden_layers``); those below ``first_k_dense_replace`` have
    the dense feed-forward."""
    d = {k: config[k] for k in KEYS}
    if (d["scoring_func"], d["norm_topk_prob"], d["seq_aux"]) != ("softmax", False, True):
        raise ValueError("this reference routes by softmax, leaves the chosen weights as "
                         "they are and balances per sequence")
    if config.get("q_lora_rank") is not None or config.get("topk_method", "greedy") != "greedy":
        raise ValueError("this reference has no low-rank query path and routes greedily")
    kept = config.get("layers_kept", list(range(d["num_hidden_layers"])))
    if len(kept) != d["num_hidden_layers"]:
        raise ValueError("layers_kept names num_hidden_layers layers")
    d["layer_types"] = ("latent",) * len(kept)
    d["ffn_types"] = tuple("dense" if i < d["first_k_dense_replace"] else "moe" for i in kept)
    d["router_num_experts"] = config.get("router_num_experts", d["n_routed_experts"])
    d["experts_held"] = (config.get("experts_held_first", 0), d["n_routed_experts"])
    d["vocab_rows"] = config.get("padded_vocab_size", d["vocab_size"])
    d["shared_intermediate_size"] = d["n_shared_experts"] * d["moe_intermediate_size"]
    return d


def make_weights(d, key, dtype=jnp.float32):
    """Random weights from ``key`` (``seed_key(seed)``): normal(0, 0.02),
    residual projections scaled by 1/sqrt(2 L); norm weights 1 + normal(0.1)
    so that every one is exercised."""
    H, L = d["hidden_size"], d["num_hidden_layers"]
    Lm, Ld = d["ffn_types"].count("moe"), d["ffn_types"].count("dense")
    nh, dn, dr, dv, rank = (d["num_attention_heads"], d["qk_nope_head_dim"],
                            d["qk_rope_head_dim"], d["v_head_dim"], d["kv_lora_rank"])
    E, Eh = d["router_num_experts"], d["experts_held"][1]
    I, F, Fs, V = (d["intermediate_size"], d["moe_intermediate_size"],
                   d["shared_intermediate_size"], d["vocab_rows"])
    k = iter(jax.random.split(key, 32))

    def n(shape, std):
        return (std * jax.random.normal(next(k), shape, jnp.float32)).astype(dtype)

    def unit(shape):
        return (1 + n(shape, 0.1).astype(jnp.float32)).astype(dtype)

    res = 0.02 / (2 * L) ** 0.5
    return {
        "embed": n((V, H), 0.02), "head": n((V, H), 0.02), "norm_f": unit((H,)),
        "norm1": unit((L, H)), "norm2": unit((L, H)),
        "attn": {
            "w_q": n((L, H, nh, dn + dr), 0.02), "w_kva": n((L, H, rank + dr), 0.02),
            "kv_norm": unit((L, rank)), "w_kvb": n((L, rank, nh, dn + dv), 0.02),
            "w_o": n((L, nh * dv, H), res),
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


# --- latent attention ---------------------------------------------------------

def mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(d):
    """The rotary frequencies of the 32 pairs under the published yarn entry."""
    dr, theta, s = d["qk_rope_head_dim"], d["rope_theta"], d["rope_scaling"]
    pair = lambda r: (dr * math.log(s["original_max_position_embeddings"] / (r * 2 * math.pi))  # noqa: E731
                      / (2 * math.log(theta)))
    low, high = max(math.floor(pair(s["beta_fast"])), 0), min(math.ceil(pair(s["beta_slow"])), dr - 1)
    i = jnp.arange(dr // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / dr)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return f * (1 - ramp) + f / s["factor"] * ramp


def score_scale(d):
    s = d["rope_scaling"]
    return ((d["qk_nope_head_dim"] + d["qk_rope_head_dim"]) ** -0.5
            * mscale(s["factor"], s["mscale_all_dim"]) ** 2)


def rotary(x, d):
    """x (S, heads, 64): halves rotated against each other by position."""
    s = d["rope_scaling"]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * yarn_frequencies(d)[None, :]
    both = mscale(s["factor"], s["mscale"]) / mscale(s["factor"], s["mscale_all_dim"])
    cos, sin = both * jnp.cos(ang)[:, None, :], both * jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, scale, q_block, precision):
    """Causal softmax attention of one sequence in blocks of query rows.
    q, k (S, nh, 192); v (S, nh, 128)."""
    S, nh, _ = q.shape
    q_block = min(q_block, S)
    cols = jnp.arange(S)

    @jax.checkpoint
    def rows(args):
        qb, start = args
        s = _mm("qhd,khd->hqk", qb, k, precision) * scale
        keep = cols[None, :] <= (start + jnp.arange(q_block))[:, None]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return _mm("hqk,khd->qhd", p, v, precision)

    out = jax.lax.map(rows, (q.reshape(S // q_block, q_block, nh, -1),
                             jnp.arange(0, S, q_block)))
    return out.reshape(S, -1)


def attention_mixer(lw, d, x, precision, q_block=256):
    """x (S, H), already normed -> (S, H)."""
    S = x.shape[0]
    nh, dn, rank, eps = (d["num_attention_heads"], d["qk_nope_head_dim"], d["kv_lora_rank"],
                         d["rms_norm_eps"])
    q = _mm("sh,hnf->snf", x, lw["w_q"], precision)
    c = _mm("sh,hf->sf", x, lw["w_kva"], precision)
    kv = _mm("sr,rnf->snf", _rms(c[:, :rank], lw["kv_norm"], eps), lw["w_kvb"], precision)
    k_pe = jnp.broadcast_to(rotary(c[:, None, rank:], d), (S, nh, c.shape[1] - rank))
    q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], d)], -1)
    k = jnp.concatenate([kv[..., :dn], k_pe], -1)
    ctx = _attention(q, k, kv[..., dn:], score_scale(d), q_block, precision)
    return _mm("sf,fh->sh", ctx, lw["w_o"], precision)


# --- the feed-forward halves (the plain RMSNorm, the SwiGLU, its token blocks
# and the ungated shared expert are ``afmoe_ref``'s) ---------------------------

def route(x, router, d, rows, precision):
    """(top-k expert ids (T, k), their weights (T, k), assignments to every
    expert of the router's width (E,), the per-sequence balance term) of
    x (T, H) = ``rows`` sequences of T / rows tokens."""
    E, k = d["router_num_experts"], d["num_experts_per_tok"]
    s = jax.nn.softmax(_mm("th,he->te", x, router, precision), axis=-1)
    top_s, top_e = jax.lax.top_k(s, k)
    chosen = jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=1)       # (T, E)
    per_row = lambda a: a.reshape(rows, -1, E)  # noqa: E731
    f = E * jnp.sum(per_row(chosen), axis=1) / (k * (x.shape[0] // rows))
    balance = jnp.mean(jnp.sum(f * jnp.mean(per_row(s), axis=1), axis=-1))
    return top_e, top_s * d["routed_scaling_factor"], jnp.sum(chosen, axis=0), balance


def expert_layer(lw, d, x, rows, precision, held=None):
    """x (T, H) -> (what the experts held add (T, H), assignments to every
    expert (E,), the balance term). The shared experts are
    :func:`shared_expert`'s."""
    first, count = d["experts_held"] if held is None else held
    top_e, top_w, counts, balance = route(x, lw["router"], d, rows, precision)

    @jax.checkpoint
    def adds(e, wg, wu, wd):
        weight = jnp.sum(jnp.where(top_e == first + e, top_w, 0.0), axis=-1)
        return weight[:, None] * _swiglu(x, wg, wu, wd, precision)

    y, _ = jax.lax.scan(lambda acc, ew: (acc + adds(*ew), None), jnp.zeros_like(x),
                        (jnp.arange(count), lw["w_gate"], lw["w_up"], lw["w_down"]))
    return y, counts, balance


# --- the model ----------------------------------------------------------------

def hidden(w, d, tokens, *, precision="float32"):
    """(final hidden states (B, S, H), assignments to every expert of the
    router's width per expert layer (Lm, E), the balance terms summed over
    the expert layers) of a batch of token ids (B, S)."""
    B, S = tokens.shape
    eps = d["rms_norm_eps"]
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    x = w["embed"][tokens]
    seen = {"moe": 0, "dense": 0}
    counts, balance = [], 0.0
    for i, ffn in enumerate(d["ffn_types"]):
        lw = jax.tree.map(lambda a, i=i: a[i], w["attn"])
        fw = jax.tree.map(lambda a, j=seen[ffn]: a[j], w[ffn])

        @jax.checkpoint
        def mix(x, lw, n1):
            # one row's scores at a time, and in the backward pass one row's
            # projections: the map keeps each row's input alone
            one = jax.checkpoint(lambda r: attention_mixer(lw, d, _rms(r, n1, eps), precision))
            return x + jax.lax.map(one, x)

        @jax.checkpoint
        def feed(x, fw, n2, ffn=ffn):
            m = _rms(x, n2, eps).reshape(B * S, -1)
            if ffn == "dense":
                y, n, bal = _by_token_blocks(lambda m: _swiglu(
                    m, fw["w_gate"], fw["w_up"], fw["w_down"], precision), m), None, 0.0
            else:
                y, n, bal = expert_layer(fw, d, m, B, precision)
                y = y + shared_expert(fw, m, precision)
            return x + y.reshape(x.shape), n, bal

        x = mix(x, lw, w["norm1"][i])
        x, n, bal = feed(x, fw, w["norm2"][i])
        seen[ffn] += 1
        if n is not None:
            counts.append(n)
            balance = balance + bal
    return _rms(x, w["norm_f"], eps), jnp.stack(counts), balance


def loss(w, d, tokens, targets, *, precision="float32", token_block=2048):
    """Mean next-token cross-entropy over a batch (B, S), the logits
    ``token_block`` tokens at a time, plus ``aux_loss_alpha`` times the
    balance terms. Returns (loss, assignments (Lm, E))."""
    B, S = tokens.shape
    x, counts, balance = hidden(w, d, tokens, precision=precision)
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
    return jnp.sum(total) / (B * S) + d["aux_loss_alpha"] * balance, counts


def train_step(w, opt, d, tokens, targets, *, lr, b1=0.9, b2=0.999, eps=1e-8,
               precision="float32"):
    """One step of plain Adam, as ``gpt_ref.train_step`` does it. Returns
    (weights, state, loss, gradients, assignments (Lm, E))."""
    (value, counts), g = jax.value_and_grad(functools.partial(
        loss, precision=precision), has_aux=True)(w, d, tokens, targets)
    t = opt["t"] + 1
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["m"], g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["v"], g)
    w = jax.tree.map(
        lambda p, m, v: p - lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps),
        w, m, v)
    return w, {"m": m, "v": v, "t": t}, value, g, counts
