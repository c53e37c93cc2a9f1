"""The plain reference of the hybrid decoder: gated delta-rule layers and
gated softmax-attention layers in a fixed period, each followed by a
sparse-expert layer with one shared expert, in jax.numpy.

Written from the published description of the ``qwen3_next`` block (the
configuration's ``source``). Float32, matmuls at ``highest`` precision, no
kernels, no cache. It imports nothing of the program under test: weights
come from :func:`make_weights` and the seed.

Per layer ``i`` (``norm(x) = x / rms(x) * (1 + w)``, no bias anywhere):
``x += mixer(norm(x)); x += moe(norm(x))``; the mixer is softmax attention
when ``(i + 1) % full_attention_interval == 0`` and the gated delta rule
otherwise.

* gated delta rule — ``[q|k|v|z] = x W_qkvz``, ``[b|a] = x W_ba``; q|k|v pass
  a causal depthwise convolution and SiLU; ``beta = sigmoid(b)``,
  ``g = -exp(A_log) softplus(a + dt_bias)``; q, k are L2-normalised, q is
  scaled by 1/sqrt(d_k); per value head (two to a key head) the state
  ``S (d_k, d_v)`` follows **token by token** (``lax.scan`` over time — the
  chunked form is the program's choice and is what this checks)

      S <- exp(g_t) S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T;  o_t = S^T q_t

  and ``y = (rmsnorm(o) w * silu(z)) W_o``.
* gated attention — ``[q|gate] = x W_q`` per head, q and k RMS-normalised per
  head (zero-centred weight), rotary on the first ``partial_rotary_factor``
  of each head, causal softmax over materialised scores in row blocks,
  ``y = (attn * sigmoid(gate)) W_o``.
* experts — ``p = softmax(x W_r)`` over the router's full width, the top
  ``k`` renormalised; the experts **held here** (``held = (first, count)`` of
  the router's width) add ``p_e W_d(silu(W_g x) * W_u x)`` for the tokens that
  chose them, the absent ones add nothing; one shared expert adds
  ``sigmoid(x w_s) shared(x)``. The load-balance term is
  ``E sum_e f_e P_e`` over the full width (``f`` the share of the T k
  assignments, ``P`` the mean probability; 1.0 when uniform).

Departures from the published model: random seeded weights, the chip's
share of the experts and of the vocabulary, no multi-token-prediction head,
documents packed into a row are not separated, the fused projections are
ordered q|k|v|z and b|a (a relabelling of the published per-head order).

``precision="float8"`` is the control of the output check only: both operands
of every matmul (the recurrence's q, k, v among them) rounded to e4m3.
"""

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference.gpt_ref import _mm, _round, adam_init, seed_key  # noqa: F401

KEYS = ("hidden_size", "num_hidden_layers", "full_attention_interval",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "partial_rotary_factor", "rope_theta", "linear_num_key_heads",
        "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
        "linear_conv_kernel_dim", "num_experts", "num_experts_per_tok",
        "moe_intermediate_size", "shared_expert_intermediate_size",
        "rms_norm_eps", "vocab_size", "norm_topk_prob")


def dims(config):
    """The sizes the reference needs, from a configuration file's keys.
    ``num_experts`` counts the experts held here; the router keeps its
    published width under ``router_num_experts`` (absent: all are held)."""
    d = {k: config[k] for k in KEYS}
    d["router_num_experts"] = config.get("router_num_experts", d["num_experts"])
    d["experts_held"] = (config.get("experts_held_first", 0), d["num_experts"])
    d["vocab_rows"] = config.get("padded_vocab_size", d["vocab_size"])
    d["aux_loss_coef"] = config.get("router_aux_loss_coef", 0.001)
    d["rotary_dim"] = int(d["head_dim"] * d["partial_rotary_factor"])
    d["layer_types"] = tuple(
        "full" if (i + 1) % d["full_attention_interval"] == 0 else "linear"
        for i in range(d["num_hidden_layers"]))
    return d


def make_weights(d, key, dtype=jnp.float32):
    """Random weights from ``key`` (``seed_key(seed)``): normal(0, 0.02),
    residual projections scaled by 1/sqrt(2 L); norm weights perturbed so
    that every term is exercised; the decay's ``A ~ U(1, 16)`` and
    ``dt ~ logU(0.001, 0.1)`` (``dt_bias`` its inverse softplus), the
    initialisation of the published gated delta-rule layers."""
    H, L = d["hidden_size"], d["num_hidden_layers"]
    Lg, La = d["layer_types"].count("linear"), d["layer_types"].count("full")
    nh, nkv, dh = d["num_attention_heads"], d["num_key_value_heads"], d["head_dim"]
    hk, hv = d["linear_num_key_heads"], d["linear_num_value_heads"]
    dk, dv, cw = d["linear_key_head_dim"], d["linear_value_head_dim"], d["linear_conv_kernel_dim"]
    E, Eh = d["router_num_experts"], d["experts_held"][1]
    F, Fs, V = d["moe_intermediate_size"], d["shared_expert_intermediate_size"], d["vocab_rows"]
    k = iter(jax.random.split(key, 40))

    def n(shape, std):
        return (std * jax.random.normal(next(k), shape, jnp.float32)).astype(dtype)

    def u(shape, lo, hi):
        return jax.random.uniform(next(k), shape, jnp.float32, lo, hi)

    res = 0.02 / (2 * L) ** 0.5
    dt = jnp.exp(u((Lg, hv), jnp.log(0.001), jnp.log(0.1)))
    return {
        "embed": n((V, H), 0.02), "head": n((V, H), 0.02), "norm_f": n((H,), 0.1),
        "norm1": n((L, H), 0.1), "norm2": n((L, H), 0.1),
        "gdn": {
            "w_qkvz": n((Lg, H, 2 * hk * dk + 2 * hv * dv), 0.02),
            "w_ba": n((Lg, H, 2 * hv), 0.02),
            "conv_w": u((Lg, cw, 2 * hk * dk + hv * dv), -0.5, 0.5).astype(dtype),
            "A_log": jnp.log(u((Lg, hv), 1.0, 16.0)).astype(dtype),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "norm_w": (1 + n((Lg, dv), 0.1).astype(jnp.float32)).astype(dtype),
            "w_o": n((Lg, hv * dv, H), res),
        },
        "attn": {
            "w_q": n((La, H, 2 * nh * dh), 0.02), "w_k": n((La, H, nkv * dh), 0.02),
            "w_v": n((La, H, nkv * dh), 0.02),
            "q_norm": n((La, dh), 0.1), "k_norm": n((La, dh), 0.1),
            "w_o": n((La, nh * dh, H), res),
        },
        "moe": {
            "router": n((L, H, E), 0.02),
            "w_gate": n((L, Eh, H, F), 0.02), "w_up": n((L, Eh, H, F), 0.02),
            "w_down": n((L, Eh, F, H), res),
            "shared_gate": n((L, H, Fs), 0.02), "shared_up": n((L, H, Fs), 0.02),
            "shared_down": n((L, Fs, H), res), "shared_mix": n((L, H), 0.02),
        },
    }


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + w)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


# --- the gated delta rule, token by token ------------------------------------

def causal_conv_silu(x, w):
    """Depthwise causal convolution over time and SiLU. x (S, C); w (K, C),
    the last tap on the current token."""
    K = w.shape[0]
    pad = jnp.pad(x, ((K - 1, 0), (0, 0)))
    y = sum(pad[j:j + x.shape[0]] * w[j] for j in range(K))
    return jax.nn.silu(y)


def delta_rule_recurrence(q, k, v, g, beta, time_block=128):
    """The recurrence of the module's docstring for one sequence. q, k
    (S, hv, dk) (already normalised, scaled and given to their value heads);
    v (S, hv, dv); g, beta (S, hv). Returns o (S, hv, dv). Time is scanned
    in blocks that are recomputed in the backward pass, so that one state
    a block and not one a token is kept."""
    S, hv, dk = q.shape
    dv = v.shape[-1]
    pad = -S % time_block
    if pad:
        z = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))  # noqa: E731
        q, k, v, g, beta = z(q), z(k), z(v), z(g), z(beta)

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    blocks = jax.tree.map(
        lambda a: a.reshape((a.shape[0] // time_block, time_block) + a.shape[1:]),
        (q, k, v, g, beta))
    _, o = jax.lax.scan(block, jnp.zeros((hv, dk, dv), jnp.float32), blocks)
    return o.reshape(-1, hv, dv)[:S]


def gated_delta_mixer(lw, d, x, precision):
    """x (S, H) -> (S, H)."""
    S = x.shape[0]
    hk, hv = d["linear_num_key_heads"], d["linear_num_value_heads"]
    dk, dv = d["linear_key_head_dim"], d["linear_value_head_dim"]
    qkvz = _mm("sh,hf->sf", x, lw["w_qkvz"], precision)
    ba = _mm("sh,hf->sf", x, lw["w_ba"], precision)
    qkv, z = jnp.split(qkvz, [2 * hk * dk + hv * dv], axis=-1)
    qkv = causal_conv_silu(qkv, lw["conv_w"])
    q, k, v = jnp.split(qkv, [hk * dk, 2 * hk * dk], axis=-1)
    b, a = jnp.split(ba, 2, axis=-1)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(lw["A_log"]) * jax.nn.softplus(a + lw["dt_bias"])
    rep = hv // hk
    q = jnp.repeat(_l2(q.reshape(S, hk, dk)) / dk ** 0.5, rep, axis=1)
    k = jnp.repeat(_l2(k.reshape(S, hk, dk)), rep, axis=1)
    o = delta_rule_recurrence(_round(q, precision), _round(k, precision),
                              _round(v.reshape(S, hv, dv), precision), g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + d["rms_norm_eps"]) * lw["norm_w"]
    o = o * jax.nn.silu(z.reshape(S, hv, dv))
    return _mm("sf,fh->sh", o.reshape(S, hv * dv), lw["w_o"], precision)


# --- gated softmax attention -------------------------------------------------

def rotary(x, d):
    """Rotary embedding on the first ``rotary_dim`` features of each head
    (halves rotated against each other). x (S, heads, dh)."""
    rot = d["rotary_dim"]
    inv = 1.0 / d["rope_theta"] ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _attention(q, k, v, q_block, precision):
    """Causal softmax attention of one sequence in blocks of query rows.
    q (S, nh, d); k, v (S, nkv, d)."""
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


def gated_attention_mixer(lw, d, x, precision, q_block=512):
    S = x.shape[0]
    nh, nkv, dh, eps = (d["num_attention_heads"], d["num_key_value_heads"],
                        d["head_dim"], d["rms_norm_eps"])
    qg = _mm("sh,hf->sf", x, lw["w_q"], precision).reshape(S, nh, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = _mm("sh,hf->sf", x, lw["w_k"], precision).reshape(S, nkv, dh)
    v = _mm("sh,hf->sf", x, lw["w_v"], precision).reshape(S, nkv, dh)
    q = rotary(_norm(q, lw["q_norm"], eps), d)
    k = rotary(_norm(k, lw["k_norm"], eps), d)
    ctx = _attention(q, k, v, q_block, precision)
    ctx = ctx * jax.nn.sigmoid(gate.reshape(S, nh * dh))
    return _mm("sf,fh->sh", ctx, lw["w_o"], precision)


# --- the expert layer --------------------------------------------------------

def route(x, router, d, precision):
    """(top-k expert ids (T, k), their weights (T, k), load-balance term,
    assignments to every expert of the router's width (E,))."""
    E, k = d["router_num_experts"], d["num_experts_per_tok"]
    p = jax.nn.softmax(_mm("th,he->te", x, router, precision), axis=-1)
    top_p, top_e = jax.lax.top_k(p, k)
    if d["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    counts = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0)
    share = counts / (x.shape[0] * k)
    aux = E * jnp.sum(jax.lax.stop_gradient(share) * jnp.mean(p, axis=0))
    return top_e, top_p, aux, counts


def expert_layer(lw, d, x, precision, held=None):
    """x (T, H) -> (what the experts held and the shared expert add (T, H),
    load-balance term, assignments to each expert held)."""
    first, count = d["experts_held"] if held is None else held
    top_e, top_p, aux, counts = route(x, lw["router"], d, precision)

    @jax.checkpoint
    def adds(e, wg, wu, wd):
        weight = jnp.sum(jnp.where(top_e == first + e, top_p, 0.0), axis=-1)
        h = jax.nn.silu(_mm("th,hf->tf", x, wg, precision)) * _mm("th,hf->tf", x, wu, precision)
        return weight[:, None] * _mm("tf,fh->th", h, wd, precision)

    y, _ = jax.lax.scan(lambda acc, ew: (acc + adds(*ew), None), jnp.zeros_like(x),
                        (jnp.arange(count), lw["w_gate"], lw["w_up"], lw["w_down"]))
    return y, aux, jax.lax.dynamic_slice(counts, (first,), (count,))


def shared_expert(lw, x, precision):
    h = (jax.nn.silu(_mm("th,hf->tf", x, lw["shared_gate"], precision))
         * _mm("th,hf->tf", x, lw["shared_up"], precision))
    mix = jax.nn.sigmoid(jnp.sum(x * lw["shared_mix"], -1, keepdims=True))
    return mix * _mm("tf,fh->th", h, lw["shared_down"], precision)


# --- the model ----------------------------------------------------------------

def hidden(w, d, tokens, *, precision="float32"):
    """Final hidden states (B, S, H) of a batch of token ids (B, S), the
    mean load-balance term of the layers and the assignments to each expert
    held, per layer (L, count)."""
    B, S = tokens.shape
    eps = d["rms_norm_eps"]
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    x = w["embed"][tokens]
    seen = {"linear": 0, "full": 0}
    aux_sum, loads = 0.0, []
    for i, kind in enumerate(d["layer_types"]):
        group, mixer = (("gdn", gated_delta_mixer) if kind == "linear"
                        else ("attn", gated_attention_mixer))
        lw = jax.tree.map(lambda a, j=seen[kind]: a[j], w[group])
        seen[kind] += 1
        mw = jax.tree.map(lambda a, i=i: a[i], w["moe"])

        @jax.checkpoint
        def mix(x, lw, n1, mixer=mixer, at_once=kind == "linear"):
            # the recurrence walks every row at once, the score blocks one row at a time
            one = lambda r: mixer(lw, d, _norm(r, n1, eps), precision)  # noqa: E731
            return x + (jax.vmap(one)(x) if at_once else jax.lax.map(one, x))

        @jax.checkpoint
        def experts(x, mw, n2):
            h = _norm(x, n2, eps).reshape(B * S, -1)
            y, aux, load = expert_layer(mw, d, h, precision)
            y = y + shared_expert(mw, h, precision)
            return x + y.reshape(x.shape), aux, load

        x = mix(x, lw, w["norm1"][i])
        x, aux, load = experts(x, mw, w["norm2"][i])
        aux_sum = aux_sum + aux
        loads.append(load)
    return _norm(x, w["norm_f"], eps), aux_sum / len(d["layer_types"]), jnp.stack(loads)


def loss(w, d, tokens, targets, *, precision="float32", row_block=1):
    """Mean next-token cross-entropy over a batch (B, S) plus the
    load-balance term at its coefficient; the logits ``row_block`` rows at a
    time. Returns (loss, assignments (L, count))."""
    B, S = tokens.shape
    x, aux, loads = hidden(w, d, tokens, precision=precision)
    head = w["head"].astype(jnp.float32)

    @jax.checkpoint
    def rows(args):
        xb, tgt = args
        lg = _mm("bsh,vh->bsv", xb, head, precision)
        return jnp.sum(jax.nn.logsumexp(lg, -1)
                       - jnp.take_along_axis(lg, tgt[..., None], -1)[..., 0])

    total = jax.lax.map(rows, (x.reshape(B // row_block, row_block, S, -1),
                               targets.reshape(B // row_block, row_block, S)))
    return jnp.sum(total) / (B * S) + d["aux_loss_coef"] * aux, loads


def train_step(w, opt, d, tokens, targets, *, lr, b1=0.9, b2=0.999, eps=1e-8,
               precision="float32", row_block=1):
    """One step of plain Adam, as ``gpt_ref.train_step`` does it. Returns
    (weights, state, loss, gradients, assignments)."""
    (value, loads), g = jax.value_and_grad(functools.partial(
        loss, precision=precision, row_block=row_block), has_aux=True)(w, d, tokens, targets)
    t = opt["t"] + 1
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["m"], g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["v"], g)
    w = jax.tree.map(
        lambda p, m, v: p - lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps),
        w, m, v)
    return w, {"m": m, "v": v, "t": t}, value, g, loads
