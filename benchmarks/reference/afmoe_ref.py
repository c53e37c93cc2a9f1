"""The plain reference of the ``afmoe`` decoder (Arcee Trinity): gated
softmax attention over a sliding window on three layers of four and over all
keys on the fourth, four RMSNorms a block, leading dense SwiGLU layers and
then sparse experts routed by sigmoid scores under a selection bias, in
jax.numpy.

Written from the published description of the ``afmoe`` block (the
configuration's ``source``; what has no key there is listed under the
configuration file's ``assumed``). Float32, matmuls at ``highest``
precision, no kernels, no cache. It imports nothing of the program under
test: weights come from :func:`make_weights` and the seed.

``rms(x; w) = w * x / sqrt(mean(x^2) + eps)`` (plain weight, at rest 1), no
bias anywhere.

* embedding — ``h = E[tokens] * sqrt(hidden)`` (``mup_enabled``).
* attention half, every layer — ``a = rms(h; w1)``; ``q = a Wq``
  (heads x d), ``k = a Wk``, ``v = a Wv`` (kv heads x d), ``g = a Wg``
  (heads x d); q and k are RMS-normalised over d (``q_norm``, ``k_norm``);
  on a ``sliding_attention`` layer rotary on all d features (halves rotated
  against each other) at ``rope_theta``, on a ``full_attention`` layer **no**
  position signal; scores ``q k^T / sqrt(d)``; key ``j`` is visible to query
  ``i`` iff ``j <= i`` and, on a sliding layer, ``i - j < sliding_window``;
  softmax over materialised scores in row blocks; ``c = softmax v``;
  ``c <- c * sigmoid(g)``; ``h <- h + rms(c Wo; w2)``.
* feed-forward half — ``m = rms(h; w3)``. A dense layer (the first
  ``num_dense_layers``): ``y = (silu(m Wgate) * m Wup) Wdown``. An expert
  layer: ``s = sigmoid(m Wr)`` over the router's full width;
  ``sel = top_k(s + b)`` with ``b`` the selection bias (no gradient);
  ``w = s[sel] / (sum s[sel] + 1e-20) * route_scale``; the experts **held
  here** (``held = (first, count)`` of the router's width) add
  ``w_e Wd(silu(Wg m) * Wu m)`` for the tokens that chose them, the absent
  ones add nothing; the shared expert adds ``shared(m)``, ungated. Then
  ``h <- h + rms(y; w4)``.
* head — ``rms(h; wf)``, an untied output matrix, mean next-token
  cross-entropy over the vocabulary held. No auxiliary loss: after each step
  ``b <- b + load_balance_coeff * sign(mean(n) - n)``, ``n`` the step's
  assignments to each expert of the router's width.

Departures from the published model: random seeded weights, the chip's share
of the experts and of the vocabulary, the layers kept (``layers_kept``),
documents packed into a row are not separated, ``n`` counts this chip's
tokens (in the deployment it is summed over the data-parallel group).

``precision="float8"`` is the control of the output check only: both operands
of every matmul (attention's q, k, v among them) rounded to e4m3.
"""

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference.gpt_ref import _mm, adam_init, seed_key  # noqa: F401

KEYS = ("hidden_size", "num_hidden_layers", "num_dense_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "sliding_window", "rope_theta",
        "intermediate_size", "moe_intermediate_size", "num_experts", "num_experts_per_tok",
        "num_shared_experts", "route_norm", "route_scale", "score_func",
        "load_balance_coeff", "mup_enabled", "rms_norm_eps", "vocab_size")
KINDS = {"sliding_attention": "window", "full_attention": "full"}


def dims(config):
    """The sizes the reference needs, from a configuration file's keys.
    ``num_experts`` counts the experts held here; the router keeps its
    published width under ``router_num_experts`` (absent: all are held).
    ``layer_types`` stays the published list; ``layers_kept`` names the
    published layers that are here (absent: the first ``num_hidden_layers``),
    of which the first ``num_dense_layers`` have the dense feed-forward."""
    d = {k: config[k] for k in KEYS}
    if d["score_func"] != "sigmoid":
        raise ValueError(f"score_func {d['score_func']!r}: this reference scores by sigmoid")
    kept = config.get("layers_kept", list(range(d["num_hidden_layers"])))
    if len(kept) != d["num_hidden_layers"]:
        raise ValueError("layers_kept names num_hidden_layers layers")
    d["layer_types"] = tuple(KINDS[config["layer_types"][i]] for i in kept)
    d["ffn_types"] = tuple("dense" if p < d["num_dense_layers"] else "moe"
                           for p in range(len(kept)))
    d["router_num_experts"] = config.get("router_num_experts", d["num_experts"])
    d["experts_held"] = (config.get("experts_held_first", 0), d["num_experts"])
    d["vocab_rows"] = config.get("padded_vocab_size", d["vocab_size"])
    d["shared_intermediate_size"] = d["num_shared_experts"] * d["moe_intermediate_size"]
    d["embed_scale"] = d["hidden_size"] ** 0.5 if d["mup_enabled"] else 1.0
    return d


def make_weights(d, key, dtype=jnp.float32):
    """Random weights from ``key`` (``seed_key(seed)``): normal(0, 0.02),
    residual projections scaled by 1/sqrt(2 L); norm weights 1 + normal(0.1)
    so that every one is exercised."""
    H, L = d["hidden_size"], d["num_hidden_layers"]
    Lm, Ld = d["ffn_types"].count("moe"), d["ffn_types"].count("dense")
    nh, nkv, dh = d["num_attention_heads"], d["num_key_value_heads"], d["head_dim"]
    E, Eh = d["router_num_experts"], d["experts_held"][1]
    I, F, Fs, V = (d["intermediate_size"], d["moe_intermediate_size"],
                   d["shared_intermediate_size"], d["vocab_rows"])
    k = iter(jax.random.split(key, 40))

    def n(shape, std):
        return (std * jax.random.normal(next(k), shape, jnp.float32)).astype(dtype)

    def unit(shape):
        return (1 + n(shape, 0.1).astype(jnp.float32)).astype(dtype)

    res = 0.02 / (2 * L) ** 0.5
    return {
        "embed": n((V, H), 0.02), "head": n((V, H), 0.02), "norm_f": unit((H,)),
        "norm1": unit((L, H)), "norm1_post": unit((L, H)),
        "norm2": unit((L, H)), "norm2_post": unit((L, H)),
        "attn": {
            "w_q": n((L, H, nh * dh), 0.02), "w_gate": n((L, H, nh * dh), 0.02),
            "w_k": n((L, H, nkv * dh), 0.02), "w_v": n((L, H, nkv * dh), 0.02),
            "q_norm": unit((L, dh)), "k_norm": unit((L, dh)),
            "w_o": n((L, nh * dh, H), res),
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


def bias_init(d):
    """The selection bias at rest: (expert layers, router width) zeros."""
    return jnp.zeros((d["ffn_types"].count("moe"), d["router_num_experts"]), jnp.float32)


def _rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


# --- gated attention, windowed or full ----------------------------------------

def rotary(x, theta):
    """Rotary embedding on every feature of each head (halves rotated
    against each other). x (S, heads, d)."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window, q_block, precision):
    """Softmax attention of one sequence under an explicit mask, in blocks
    of query rows: key j is visible to query i iff ``j <= i`` and (``window``
    not None) ``i - j < window``. q (S, nh, d); k, v (S, nkv, d)."""
    S, nh, dh = q.shape
    nkv = k.shape[1]
    q = q.reshape(S, nkv, nh // nkv, dh)
    q_block = min(q_block, S)
    cols = jnp.arange(S)

    @jax.checkpoint
    def rows(args):
        qb, start = args
        s = _mm("qgrd,kgd->grqk", qb, k, precision) / dh ** 0.5
        i = (start + jnp.arange(q_block))[:, None]
        keep = cols[None, :] <= i
        if window is not None:
            keep = keep & (i - cols[None, :] < window)
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return _mm("grqk,kgd->qgrd", p, v, precision)

    out = jax.lax.map(rows, (q.reshape(S // q_block, q_block, nkv, nh // nkv, dh),
                             jnp.arange(0, S, q_block)))
    return out.reshape(S, nh * dh)


def attention_mixer(lw, d, x, kind, precision, q_block=256):
    """x (S, H), already normed -> (S, H), before the output norm."""
    S = x.shape[0]
    nh, nkv, dh, eps = (d["num_attention_heads"], d["num_key_value_heads"],
                        d["head_dim"], d["rms_norm_eps"])
    q = _mm("sh,hf->sf", x, lw["w_q"], precision).reshape(S, nh, dh)
    k = _mm("sh,hf->sf", x, lw["w_k"], precision).reshape(S, nkv, dh)
    v = _mm("sh,hf->sf", x, lw["w_v"], precision).reshape(S, nkv, dh)
    gate = _mm("sh,hf->sf", x, lw["w_gate"], precision)
    q, k = _rms(q, lw["q_norm"], eps), _rms(k, lw["k_norm"], eps)
    window = None
    if kind == "window":
        q, k = rotary(q, d["rope_theta"]), rotary(k, d["rope_theta"])
        window = d["sliding_window"]
    ctx = _attention(q, k, v, window, q_block, precision) * jax.nn.sigmoid(gate)
    return _mm("sf,fh->sh", ctx, lw["w_o"], precision)


# --- the feed-forward halves --------------------------------------------------

def _swiglu(x, wg, wu, wd, precision):
    h = jax.nn.silu(_mm("th,hf->tf", x, wg, precision)) * _mm("th,hf->tf", x, wu, precision)
    return _mm("tf,fh->th", h, wd, precision)


def _by_token_blocks(f, x, block=2048):
    """``f`` over x (T, H) a block of tokens at a time, recomputed in the
    backward pass: the wide hidden of a feed-forward never stands whole."""
    T = x.shape[0]
    if T <= block or T % block:
        return f(x)
    return jax.lax.map(jax.checkpoint(f), x.reshape(T // block, block, -1)).reshape(T, -1)


def route(x, router, bias, d, precision):
    """(top-k expert ids (T, k), their weights (T, k), assignments to every
    expert of the router's width (E,)). The bias moves the choice and not
    the weights."""
    E, k = d["router_num_experts"], d["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm("th,he->te", x, router, precision))
    _, top_e = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    if d["route_norm"]:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    counts = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0)
    return top_e, top_s * d["route_scale"], counts


def expert_layer(lw, bias, d, x, precision, held=None):
    """x (T, H) -> (what the experts held add (T, H), assignments to every
    expert (E,), the experts each token chose (T, k)). The shared expert is
    :func:`shared_expert`'s."""
    first, count = d["experts_held"] if held is None else held
    top_e, top_w, counts = route(x, lw["router"], bias, d, precision)

    @jax.checkpoint
    def adds(e, wg, wu, wd):
        weight = jnp.sum(jnp.where(top_e == first + e, top_w, 0.0), axis=-1)
        return weight[:, None] * _swiglu(x, wg, wu, wd, precision)

    y, _ = jax.lax.scan(lambda acc, ew: (acc + adds(*ew), None), jnp.zeros_like(x),
                        (jnp.arange(count), lw["w_gate"], lw["w_up"], lw["w_down"]))
    return y, counts, top_e


def shared_expert(lw, x, precision):
    return _by_token_blocks(lambda m: _swiglu(
        m, lw["shared_gate"], lw["shared_up"], lw["shared_down"], precision), x)


# --- the model ----------------------------------------------------------------

def hidden(w, bias, d, tokens, *, precision="float32", with_chosen=False):
    """Final hidden states (B, S, H) of a batch of token ids (B, S) and the
    assignments to every expert of the router's width, per expert layer
    (Lm, E); ``with_chosen`` adds the experts every token chose (Lm, B S, k).
    A ``bias`` of (Lm, B S, E) biases every token's choice by itself: ten on
    a token's chosen experts pins another run to this one's routing."""
    B, S = tokens.shape
    eps = d["rms_norm_eps"]
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    x = w["embed"][tokens] * d["embed_scale"]
    seen = {"moe": 0, "dense": 0}
    counts, chosen = [], []
    for i, (kind, ffn) in enumerate(zip(d["layer_types"], d["ffn_types"])):
        lw = jax.tree.map(lambda a, i=i: a[i], w["attn"])
        fw = jax.tree.map(lambda a, j=seen[ffn]: a[j], w[ffn])

        @jax.checkpoint
        def mix(x, lw, n1, n1_post, kind=kind):
            # one row's scores at a time, and in the backward pass one row's
            # projections: the map keeps each row's input alone
            one = jax.checkpoint(lambda r: _rms(attention_mixer(
                lw, d, _rms(r, n1, eps), kind, precision), n1_post, eps))
            return x + jax.lax.map(one, x)

        @jax.checkpoint
        def feed(x, fw, b, n2, n2_post, ffn=ffn):
            m = _rms(x, n2, eps).reshape(B * S, -1)
            if ffn == "dense":
                y, n, e = _by_token_blocks(lambda m: _swiglu(
                    m, fw["w_gate"], fw["w_up"], fw["w_down"], precision), m), None, None
            else:
                y, n, e = expert_layer(fw, b, d, m, precision)
                y = y + shared_expert(fw, m, precision)
            return x + _rms(y, n2_post, eps).reshape(x.shape), n, e

        x = mix(x, lw, w["norm1"][i], w["norm1_post"][i])
        b = bias[seen["moe"]] if ffn == "moe" else None
        x, n, e = feed(x, fw, b, w["norm2"][i], w["norm2_post"][i])
        seen[ffn] += 1
        if n is not None:
            counts.append(n)
            chosen.append(e)
    out = _rms(x, w["norm_f"], eps), jnp.stack(counts)
    return out + (jnp.stack(chosen),) if with_chosen else out


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


def bias_update(bias, counts, d):
    """``b + load_balance_coeff * sign(mean(n) - n)``, layer by layer."""
    return bias + d["load_balance_coeff"] * jnp.sign(
        jnp.mean(counts, axis=-1, keepdims=True) - counts)


def train_step(w, opt, bias, d, tokens, targets, *, lr, b1=0.9, b2=0.999, eps=1e-8,
               precision="float32"):
    """One step of plain Adam, as ``gpt_ref.train_step`` does it, and the
    bias's own step. Returns (weights, state, bias, loss, gradients,
    assignments (Lm, E))."""
    (value, counts), g = jax.value_and_grad(functools.partial(
        loss, precision=precision), has_aux=True)(w, bias, d, tokens, targets)
    t = opt["t"] + 1
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["m"], g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["v"], g)
    w = jax.tree.map(
        lambda p, m, v: p - lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps),
        w, m, v)
    return w, {"m": m, "v": v, "t": t}, bias_update(bias, counts, d), value, g, counts
