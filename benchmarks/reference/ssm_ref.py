"""The plain reference of the ``nemotron_h`` decoder (NVIDIA-Nemotron-3-Nano):
layers of ONE half each — Mamba-2 state-space mixers, ungated ``relu(x)^2``
experts routed by a sigmoid top-k under a selection bias beside one shared
expert, and grouped-query attention without a position signal — in the order
of the published ``hybrid_override_pattern``, in jax.numpy.

Written from the published configuration (the configuration's ``source``) and
the family's modelling code; what has no key there is listed under the
configuration file's ``assumed``. Float32, matmuls at ``highest`` precision,
no kernels, no cache, the state-space layer token by token. It imports nothing
of the program under test: weights come from :func:`make_weights` and the seed.

``rms(x; w) = w * x / sqrt(mean(x^2) + eps)`` (plain weight, at rest 1), no
bias on any linear, every linear stored (in, out). Every layer is ``h <- h +
mixer(rms(h; w_i))`` with the mixer its letter names:

* ``M`` — ``z | xBC | dt = m W_in`` (widths ``d_inner`` = heads x head_dim |
  ``d_inner + 2 groups x state`` | heads); ``xBC = silu(conv(xBC) + b)``, a
  depthwise causal convolution of ``conv_kernel`` taps (the last on the
  current token); ``x | B | C = xBC``; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``, both a head. Per head with the ``B``, ``C`` of its group and
  float32 state ``S`` (state x head_dim), token by token: ``S <- exp(dt A) S +
  dt B x^T``, ``y = S^T C + D x``. Then ``y = rms(y * silu(z); w)`` over
  groups of ``d_inner / groups`` (gate BEFORE norm, a weight a channel),
  ``out = y W_out``.
* ``*`` — ``q = m Wq`` (heads x head_dim), ``k``, ``v`` (kv heads x
  head_dim), causal softmax at ``head_dim^-0.5``, no rotary, no gate, no q/k
  norm; ``o Wo``.
* ``E`` — ``s = sigmoid(m Wr)`` over the router's full width, the top ``k`` of
  ``s + b`` (``b`` the selection bias: state, no gradient), weights the
  chosen ``s / (sum + 1e-20) * routed_scaling_factor``; the experts **held
  here** (``held = (first, count)`` of the router's width) add ``weight *
  relu(m W_up)^2 W_down`` for the tokens that chose them, the absent ones add
  nothing; the shared expert adds the same form at its own width, ungated.
  After each step ``b <- b + rate * sign(mean(n) - n)`` over the step's
  assignments ``n`` to every expert (assumed: the row has no key for it).
  The state's starting ``b`` is that rule's rest on one seeded batch
  (:func:`balanced_bias`), which the program under test takes from here.
* head — ``rms(h; wf)``, an untied output matrix, mean next-token
  cross-entropy over the vocabulary held.

Departures from the published model: random seeded weights, the chip's share
of the experts and of the vocabulary, the layers kept (``layers_kept``),
documents packed into a row are not separated (the state and attention run
across the end-of-document token).

``precision="float8"`` is the control of the output check only: both operands
of every matmul (attention's q, k, v among them) rounded to e4m3, and the
operands of the recurrence's two products (``B x^T`` and ``S^T C``) likewise.
"""

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference.afmoe_ref import (_by_token_blocks, _rms, bias_update,  # noqa: F401
                                            route)
from benchmarks.reference.gpt_ref import (_attention, _mm, _round, adam_init,  # noqa: F401
                                          seed_key)

KEYS = ("hidden_size", "num_hidden_layers", "hybrid_override_pattern", "num_attention_heads",
        "num_key_value_heads", "head_dim", "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
        "n_groups", "conv_kernel", "chunk_size", "use_conv_bias", "moe_intermediate_size",
        "moe_shared_expert_intermediate_size", "n_routed_experts", "num_experts_per_tok",
        "n_shared_experts", "norm_topk_prob", "routed_scaling_factor", "layer_norm_epsilon",
        "vocab_size")
LETTERS = {"M": "ssm", "*": "attn", "E": "moe"}


def dims(config):
    """The sizes the reference needs, from a configuration file's keys.
    ``n_routed_experts`` counts the experts held here; the router keeps its
    published width under ``router_num_experts`` (absent: all are held).
    ``hybrid_override_pattern`` stays the published string; ``layers_kept``
    names the published layers that are here (absent: the first
    ``num_hidden_layers``)."""
    d = {k: config[k] for k in KEYS}
    if (config.get("mlp_hidden_act", "relu2") != "relu2" or config.get("n_group", 1) != 1
            or not d["use_conv_bias"]):
        raise ValueError("this reference has relu2 experts, no group-limited routing and a "
                         "bias on the state-space layers' convolution")
    kept = config.get("layers_kept", list(range(d["num_hidden_layers"])))
    if len(kept) != d["num_hidden_layers"]:
        raise ValueError("layers_kept names num_hidden_layers layers")
    d["kinds"] = tuple(LETTERS[d["hybrid_override_pattern"][i]] for i in kept)
    d["router_num_experts"] = config.get("router_num_experts", d["n_routed_experts"])
    d["experts_held"] = (config.get("experts_held_first", 0), d["n_routed_experts"])
    d["vocab_rows"] = config.get("padded_vocab_size", d["vocab_size"])
    d["shared_intermediate_size"] = (d["n_shared_experts"]
                                     * d["moe_shared_expert_intermediate_size"])
    d["load_balance_coeff"] = config["bias_update_rate"]
    # ``afmoe_ref.route``'s names for the same router
    d["route_norm"], d["route_scale"] = d["norm_topk_prob"], d["routed_scaling_factor"]
    d["d_inner"] = d["mamba_num_heads"] * d["mamba_head_dim"]
    d["conv_dim"] = d["d_inner"] + 2 * d["n_groups"] * d["ssm_state_size"]
    return d


def make_weights(d, key, dtype=jnp.float32):
    """Random weights from ``key`` (``seed_key(seed)``): normal(0, 0.02),
    output projections scaled by 1/sqrt(2 L); norm weights 1 + normal(0.1)
    so that every one is exercised; the state-space layers' own start values
    as published (``dt`` log-uniform in [time_step_min, time_step_max] = [1e-3,
    1e-1] floored at 1e-4, through the inverse softplus; ``A`` uniform in
    [1, 16]; ``D`` = 1) and their convolution uniform in +-0.5."""
    H, L = d["hidden_size"], d["num_hidden_layers"]
    Ls, La, Lm = (d["kinds"].count(kind) for kind in ("ssm", "attn", "moe"))
    nh, nkv, dh = d["num_attention_heads"], d["num_key_value_heads"], d["head_dim"]
    mh, inner, conv = d["mamba_num_heads"], d["d_inner"], d["conv_dim"]
    E, Eh = d["router_num_experts"], d["experts_held"][1]
    F, Fs, V = d["moe_intermediate_size"], d["shared_intermediate_size"], d["vocab_rows"]
    k = iter(jax.random.split(key, 32))

    def n(shape, std):
        return (std * jax.random.normal(next(k), shape, jnp.float32)).astype(dtype)

    def unit(shape):
        return (1 + n(shape, 0.1).astype(jnp.float32)).astype(dtype)

    res = 0.02 / (2 * L) ** 0.5
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        next(k), (Ls, mh), jnp.float32, jnp.log(1e-3), jnp.log(1e-1))), 1e-4)
    a = jax.random.uniform(next(k), (Ls, mh), jnp.float32, 1.0, 16.0)
    return {
        "embed": n((V, H), 0.02), "head": n((V, H), 0.02), "norm_f": unit((H,)),
        "norm": unit((L, H)),
        "ssm": {
            "w_in": n((Ls, H, inner + conv + mh), 0.02),
            "conv_w": jax.random.uniform(next(k), (Ls, d["conv_kernel"], conv), jnp.float32,
                                         -0.5, 0.5).astype(dtype),
            "conv_b": n((Ls, conv), 0.02),
            "A_log": jnp.log(a), "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": jnp.ones((Ls, mh), jnp.float32), "norm_w": unit((Ls, inner)),
            "w_out": n((Ls, inner, H), res),
        },
        "attn": {
            "w_q": n((La, H, nh * dh), 0.02), "w_k": n((La, H, nkv * dh), 0.02),
            "w_v": n((La, H, nkv * dh), 0.02), "w_o": n((La, nh * dh, H), res),
        },
        "moe": {
            "router": n((Lm, H, E), 0.02),
            "w_up": n((Lm, Eh, H, F), 0.02), "w_down": n((Lm, Eh, F, H), res),
            "shared_up": n((Lm, H, Fs), 0.02), "shared_down": n((Lm, Fs, H), res),
        },
    }


def bias_init(d):
    """The selection bias at rest: (expert layers, router width) zeros."""
    return jnp.zeros((d["kinds"].count("moe"), d["router_num_experts"]), jnp.float32)


# --- the state-space mixer ----------------------------------------------------

def ssm_recurrence(x, dt, A, B, C, D, precision="float32", time_block=64):
    """The recurrence of the module's docstring over a batch, token by
    token. x (R, S, heads, P); dt (R, S, heads); A, D (heads,); B, C (R, S,
    groups, N). Returns y (R, S, heads, P). Time is scanned in blocks that
    are recomputed in the backward pass, so that one state a block and not
    one a token is kept; the state is held (R, groups, heads a group, P, N)
    — a group's ``B`` and ``C`` meet its heads by broadcasting, and the minor
    axis is a whole lane tile."""
    R, S, nh, P = x.shape
    G, N = B.shape[2:]
    hg = nh // G
    xs = (x.reshape(R, S, nh * P), dt, B, C)
    pad = -S % time_block
    if pad:
        xs = tuple(jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in xs)
    lo = lambda a: _round(a, precision)  # noqa: E731
    A, D = A.reshape(G, hg), D.reshape(G, hg)

    def token(state, inputs):
        x_t, dt_t, b_t, c_t = inputs                   # (R, heads P), (R, heads), (R, G, N) x 2
        x_t, dt_t = x_t.reshape(R, G, hg, P), dt_t.reshape(R, G, hg)
        wrote = ((dt_t[..., None] * lo(x_t))[..., None]
                 * lo(b_t)[:, :, None, None, :])       # dt B x^T, (R, G, hg, P, N)
        state = jnp.exp(dt_t * A)[..., None, None] * state + wrote
        y = jnp.einsum("rghpn,rgn->rghp", lo(state), lo(c_t),
                       precision=jax.lax.Precision.HIGHEST)
        return state, (y + D[..., None] * x_t).reshape(R, nh * P)

    @jax.checkpoint
    def block(state, inputs):
        return jax.lax.scan(token, state, inputs)

    by_block = lambda a: jnp.moveaxis(a, 1, 0).reshape(  # noqa: E731
        (a.shape[1] // time_block, time_block, R) + a.shape[2:])
    _, y = jax.lax.scan(block, jnp.zeros((R, G, hg, P, N), jnp.float32),
                        tuple(by_block(a) for a in xs))
    return jnp.moveaxis(y.reshape((-1, R, nh, P)), 0, 1)[:, :S]


def causal_conv(x, w, b):
    """x (R, S, C); w (taps, C), the last tap on the current token; b (C,)."""
    taps, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + S] * w[j] for j in range(taps)) + b


def ssm_mixer(lw, d, x, precision):
    """x (R, S, H), already normed -> (R, S, H)."""
    R, S, _ = x.shape
    mh, P, G, N = d["mamba_num_heads"], d["mamba_head_dim"], d["n_groups"], d["ssm_state_size"]
    inner, conv, eps = d["d_inner"], d["conv_dim"], d["layer_norm_epsilon"]
    proj = _mm("rsh,hf->rsf", x, lw["w_in"], precision)
    z, xbc, dt = proj[..., :inner], proj[..., inner:inner + conv], proj[..., inner + conv:]
    xbc = jax.nn.silu(causal_conv(xbc, lw["conv_w"], lw["conv_b"]))
    dt = jax.nn.softplus(dt + lw["dt_bias"])
    y = ssm_recurrence(xbc[..., :inner].reshape(R, S, mh, P), dt, -jnp.exp(lw["A_log"]),
                       xbc[..., inner:inner + G * N].reshape(R, S, G, N),
                       xbc[..., inner + G * N:].reshape(R, S, G, N), lw["D"], precision)
    y = (y.reshape(R, S, inner) * jax.nn.silu(z)).reshape(R, S, G, inner // G)
    y = _rms(y, lw["norm_w"].reshape(G, inner // G), eps).reshape(R, S, inner)
    return _mm("rsf,fh->rsh", y, lw["w_out"], precision)


# --- attention (``gpt_ref._attention``: grouped heads, causal, row blocks) ----

def attention_mixer(lw, d, x, precision, q_block=256):
    """x (S, H), already normed -> (S, H). No position signal."""
    S = x.shape[0]
    nh, nkv, dh = d["num_attention_heads"], d["num_key_value_heads"], d["head_dim"]
    q = _mm("sh,hf->sf", x, lw["w_q"], precision).reshape(S, nh, dh)
    k = _mm("sh,hf->sf", x, lw["w_k"], precision).reshape(S, nkv, dh)
    v = _mm("sh,hf->sf", x, lw["w_v"], precision).reshape(S, nkv, dh)
    return _mm("sf,fh->sh", _attention(q, k, v, q_block, precision), lw["w_o"], precision)


# --- experts ------------------------------------------------------------------

def _relu2(x, wu, wd, precision):
    h = jax.nn.relu(_mm("th,hf->tf", x, wu, precision))
    return _mm("tf,fh->th", h * h, wd, precision)


def expert_layer(lw, bias, d, x, precision, held=None):
    """x (T, H) -> (what the experts held add (T, H), assignments to every
    expert of the router's width (E,)); the router is ``afmoe_ref.route``."""
    first, count = d["experts_held"] if held is None else held
    top_e, top_w, counts = route(x, lw["router"], bias, d, precision)

    @jax.checkpoint
    def adds(e, wu, wd):
        weight = jnp.sum(jnp.where(top_e == first + e, top_w, 0.0), axis=-1)
        return weight[:, None] * _relu2(x, wu, wd, precision)

    y, _ = jax.lax.scan(lambda acc, ew: (acc + adds(*ew), None), jnp.zeros_like(x),
                        (jnp.arange(count), lw["w_up"], lw["w_down"]))
    return y, counts


def shared_expert(lw, x, precision):
    return _by_token_blocks(lambda m: _relu2(
        m, lw["shared_up"], lw["shared_down"], precision), x)


# --- the model ----------------------------------------------------------------

def hidden(w, bias, d, tokens, *, precision="float32", settle=None):
    """Final hidden states (B, S, H) of a batch of token ids (B, S) and the
    assignments to every expert of the router's width, per expert layer
    (Lm, E). With ``settle`` (:func:`balanced_bias`'s) an expert layer first
    moves its row of ``bias`` to rest on its own input, and the rows come
    back in the assignments' place."""
    B, S = tokens.shape
    eps = d["layer_norm_epsilon"]
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    x = w["embed"][tokens]
    seen = {"ssm": 0, "attn": 0, "moe": 0}
    counts = []
    for i, kind in enumerate(d["kinds"]):
        lw = jax.tree.map(lambda a, j=seen[kind]: a[j], w[kind])

        @jax.checkpoint
        def layer(x, lw, b, n1, kind=kind):
            m = _rms(x, n1, eps)
            if kind == "ssm":      # one row's projections and states at a time
                one = jax.checkpoint(lambda r: ssm_mixer(lw, d, r[None], precision)[0])
                return x + jax.lax.map(one, m), None
            if kind == "attn":     # one row's scores at a time
                one = jax.checkpoint(lambda r: attention_mixer(lw, d, r, precision))
                return x + jax.lax.map(one, m), None
            m = m.reshape(B * S, -1)
            if settle is not None:
                b = settle(m, lw["router"], b)
            y, n = expert_layer(lw, b, d, m, precision)
            return x + (y + shared_expert(lw, m, precision)).reshape(x.shape), (
                n if settle is None else b)

        b = bias[seen["moe"]] if kind == "moe" else None
        x, n = layer(x, lw, b, w["norm"][i])
        seen[kind] += 1
        if n is not None:
            counts.append(n)
    return _rms(x, w["norm_f"], eps), jnp.stack(counts)


def balanced_bias(w, d, tokens, iterations, first_rate, *, precision="float32"):
    """The selection bias (Lm, E) that the update rule leaves at rest on
    ``tokens``: from zero, ``iterations`` moves of ``b + rate * sign(mean(n)
    - n)``, the rate falling geometrically from ``first_rate`` to the step's
    own. A layer at a time, in the layers' order: a layer's scores do not
    depend on its own bias, so one pass over the layers serves every move,
    and the layers after it read what it adds at the bias it came to rest
    on."""
    decay = (d["load_balance_coeff"] / first_rate) ** (1.0 / max(iterations - 1, 1))

    def settle(m, router, b):
        def move(i, b):
            counts = route(m, router, b, d, precision)[2]
            return bias_update(b, counts, {"load_balance_coeff": first_rate * decay ** i})

        return jax.lax.fori_loop(0, iterations, move, b)

    return hidden(w, bias_init(d), d, tokens, precision=precision, settle=settle)[1]


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
