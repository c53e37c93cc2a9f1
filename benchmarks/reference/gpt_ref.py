"""The plain reference: a GPT-2 / GPT-BigCode decoder block in jax.numpy.

Written from the published descriptions (GPT-2: Radford et al. 2019; the
``gpt_bigcode`` variant adds multi-query attention: one key/value head of
the same size as a query head, shared by every query head). Float32, matmuls
at ``highest`` precision, no kernels, no cache, no batching tricks. It
imports nothing of the program under test and takes no array the program
made: weights come from :func:`make_weights` and the seed.

Layout (layers stacked on a leading axis, ``x @ W`` convention, the fused
``c_attn`` of both published models — features ordered q | k | v):

    wte (V, H)   wpe (P, H)   lnf_g, lnf_b (H,)
    ln1_g, ln1_b, ln2_g, ln2_b (L, H)
    w_qkv (L, H, (n_head + 2 n_kv) d)   b_qkv (L, (n_head + 2 n_kv) d)
    w_o (L, H, H)  b_o (L, H)
    w_fc (L, H, F) b_fc (L, F)   w_proj (L, F, H)  b_proj (L, H)

Departures from the published models: weights are random (seeded), the
tied unembedding uses ``wte`` as both tables as published, dropout is off.

The ``precision`` argument exists for the control of the output check only:
``"float32"`` is the reference; ``"float8"`` rounds both operands of every
matmul to e4m3 (per-tensor scaled, with a straight-through gradient) and
accumulates in float32 — the result a lower precision path would give.
"""

import functools

import jax
import jax.numpy as jnp

DIMS = ("vocab_size", "n_positions", "n_embd", "n_layer", "n_head", "n_kv_head",
        "n_inner", "layer_norm_epsilon")


def dims(config):
    """The sizes the reference needs, from a configuration file's keys."""
    d = {k: config[k] for k in DIMS if k in config}
    if "n_kv_head" not in d:
        d["n_kv_head"] = 1 if config.get("multi_query") else d["n_head"]
    if d.get("n_inner") is None:
        d["n_inner"] = 4 * d["n_embd"]
    d.setdefault("layer_norm_epsilon", 1e-5)
    d["head_dim"] = d["n_embd"] // d["n_head"]
    # rows of the embedding table: the vocabulary, or (Megatron's
    # ``padded_vocab_size``) more rows that no token selects
    d["vocab_rows"] = config.get("padded_vocab_size", d["vocab_size"])
    return d


def seed_key(seed):
    """A key from any whole number (seeds run past 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def make_weights(d, key, dtype=jnp.float32):
    """Random weights from ``key`` (``seed_key(seed)``) in the layout above. GPT-2's initialisation:
    normal(0, 0.02), residual projections scaled by 1/sqrt(2 L); biases and
    LayerNorm parameters are perturbed so that every term is exercised.
    Values are rounded through ``dtype`` and returned in it."""
    H, L, F, V, P = d["n_embd"], d["n_layer"], d["n_inner"], d["vocab_rows"], d["n_positions"]
    qkv = (d["n_head"] + 2 * d["n_kv_head"]) * d["head_dim"]
    k = iter(jax.random.split(key, 16))

    def n(shape, std):
        return (std * jax.random.normal(next(k), shape, jnp.float32)).astype(dtype)

    res = 0.02 / (2 * L) ** 0.5
    return {
        "wte": n((V, H), 0.02), "wpe": n((P, H), 0.01),
        "ln1_g": (1 + n((L, H), 0.1).astype(jnp.float32)).astype(dtype),
        "ln1_b": n((L, H), 0.02),
        "w_qkv": n((L, H, qkv), 0.02), "b_qkv": n((L, qkv), 0.02),
        "w_o": n((L, H, H), res), "b_o": n((L, H), 0.02),
        "ln2_g": (1 + n((L, H), 0.1).astype(jnp.float32)).astype(dtype),
        "ln2_b": n((L, H), 0.02),
        "w_fc": n((L, H, F), 0.02), "b_fc": n((L, F), 0.02),
        "w_proj": n((L, F, H), res), "b_proj": n((L, H), 0.02),
        "lnf_g": (1 + n((H,), 0.1).astype(jnp.float32)).astype(dtype),
        "lnf_b": n((H,), 0.02),
    }


# --- arithmetic at a chosen precision -----------------------------------------

@jax.custom_vjp
def _fake_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


_fake_fp8.defvjp(lambda x: (_fake_fp8(x), None), lambda _, g: (g,))


def _round(x, precision):
    if precision == "float32":
        return x
    if precision == "float8":
        return _fake_fp8(x)
    raise ValueError(f"precision {precision!r} is not float32 or float8")


def _mm(spec, a, b, precision):
    return jnp.einsum(spec, _round(a, precision), _round(b, precision),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _attention(q, k, v, q_block, precision):
    """Causal softmax attention of one sequence, in blocks of query rows so
    that the (heads, rows, S) scores fit. q (S, nh, d); k, v (S, nkv, d)."""
    S, nh, dh = q.shape
    nkv = k.shape[1]
    q = q.reshape(S, nkv, nh // nkv, dh)
    q_block = min(q_block, S)
    cols = jnp.arange(S)

    @jax.checkpoint
    def rows(args):
        qb, start = args
        s = _mm("qgrd,kgd->grqk", qb, k, precision) / dh ** 0.5
        keep = cols[None, :] <= (start + jnp.arange(q_block))[:, None]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return _mm("grqk,kgd->qgrd", p, v, precision)

    out = jax.lax.map(rows, (q.reshape(S // q_block, q_block, nkv, nh // nkv, dh),
                             jnp.arange(0, S, q_block)))
    return out.reshape(S, nh * dh)


def hidden(w, d, tokens, *, precision="float32", q_block=512):
    """Final hidden states (S, H) of ONE sequence of token ids (S,)."""
    S = tokens.shape[0]
    nh, nkv, dh, eps = d["n_head"], d["n_kv_head"], d["head_dim"], d["layer_norm_epsilon"]
    x = w["wte"][tokens].astype(jnp.float32) + w["wpe"][:S].astype(jnp.float32)
    layers = {n: a for n, a in w.items() if a.ndim >= 2 and n not in ("wte", "wpe")}

    @jax.checkpoint
    def block(x, lw):
        lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
        h = _layer_norm(x, lw["ln1_g"], lw["ln1_b"], eps)
        qkv = _mm("sh,hf->sf", h, lw["w_qkv"], precision) + lw["b_qkv"]
        q, k, v = jnp.split(qkv, [nh * dh, (nh + nkv) * dh], axis=-1)
        ctx = _attention(q.reshape(S, nh, dh), k.reshape(S, nkv, dh),
                         v.reshape(S, nkv, dh), q_block, precision)
        x = x + _mm("sh,hf->sf", ctx, lw["w_o"], precision) + lw["b_o"]
        h = _layer_norm(x, lw["ln2_g"], lw["ln2_b"], eps)
        h = _gelu_tanh(_mm("sh,hf->sf", h, lw["w_fc"], precision) + lw["b_fc"])
        return x + _mm("sf,fh->sh", h, lw["w_proj"], precision) + lw["b_proj"], None

    x, _ = jax.lax.scan(block, x, layers)
    return _layer_norm(x, w["lnf_g"].astype(jnp.float32),
                       w["lnf_b"].astype(jnp.float32), eps)


def loss(w, d, tokens, targets, *, precision="float32", row_block=1):
    """Mean next-token cross-entropy over a batch (B, S), ``row_block`` rows
    at a time so that the (rows, S, V) logits fit."""
    B, S = tokens.shape

    @jax.checkpoint
    def rows(args):
        tok, tgt = args

        def one(t, g):
            x = hidden(w, d, t, precision=precision)
            lg = _mm("sh,vh->sv", x, w["wte"].astype(jnp.float32), precision)
            return jnp.sum(jax.nn.logsumexp(lg, -1)
                           - jnp.take_along_axis(lg, g[:, None], -1)[:, 0])
        return jnp.sum(jax.vmap(one)(tok, tgt))

    total = jax.lax.map(rows, (tokens.reshape(B // row_block, row_block, S),
                               targets.reshape(B // row_block, row_block, S)))
    return jnp.sum(total) / (B * S)


def adam_init(w):
    zeros = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), w)
    return {"m": zeros, "v": jax.tree.map(jnp.copy, zeros),
            "t": jnp.zeros((), jnp.int32)}


def train_step(w, opt, d, tokens, targets, *, lr, b1=0.9, b2=0.999, eps=1e-8,
               precision="float32", row_block=1):
    """One step of plain Adam (Kingma & Ba, bias-corrected, no weight decay)
    on float32 weights. Returns (weights, state, loss, gradients)."""
    value, g = jax.value_and_grad(functools.partial(
        loss, precision=precision, row_block=row_block))(w, d, tokens, targets)
    t = opt["t"] + 1
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["m"], g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["v"], g)
    w = jax.tree.map(
        lambda p, m, v: p - lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps),
        w, m, v)
    return w, {"m": m, "v": v, "t": t}, value, g
