"""The plain reference of the ``ouro`` decoder (ByteDance Ouro, a looped
language model): ONE stack of pre- and post-normed attention / SwiGLU blocks
walked ``total_ut_steps`` times on the same weights, an exit through the one
head after every walk, a learned exit gate, and a loss over all the exits, in
jax.numpy.

Written from the published description (the configuration's ``source``; what
has no key there is listed under the configuration file's ``assumed``).
Float32, matmuls at ``highest`` precision, no kernels, no cache. It imports
nothing of the program under test: weights come from :func:`make_weights` and
the seed.

``rms(x; w) = w * x / sqrt(mean(x^2) + eps)`` (the weight itself, at rest 1),
no bias but the gate's.

* block, on the stream ``x`` — ``a = rms(x; n1)``; ``q = a Wq``, ``k = a Wk``,
  ``v = a Wv`` (heads x d each); rotary on all d features of q and k, in
  rotate-half pairs (i, i + d / 2), at ``rope_theta``; scores ``q k^T /
  sqrt(d)`` over the keys ``j <= i``, softmax over materialised scores in row
  blocks; ``x <- x + rms((softmax v) Wo; n1_post)``. Then ``m = rms(x; n2)``;
  ``x <- x + rms((silu(m Wgate) * m Wup) Wdown; n2_post)``.
* recurrence — ``h^0 = E[tokens]``; for t = 1..T: ``h^t = rms(Stack(h^(t-1));
  n_f)``, ``Stack`` all L blocks in order, the same weights at every t.
* exits, per token — ``z^t = h^t W_head^T``; ``l^t`` the cross-entropy of
  ``z^t`` against the target; ``g^t = sigmoid(h^t . w_g + b_g)``;
  ``p^1 = g^1``, ``p^t = g^t prod_{j<t} (1 - g^j)``, ``p^T = prod_{j<T}
  (1 - g^j)`` (the last walk takes what is left; ``g^T`` is never computed).
* objective — ``mean_i [ sum_t p^t l^t + beta sum_t p^t log p^t ]``: the
  expected loss under the exit distribution less ``beta`` times its entropy.

Departures from the published model: random seeded weights; the depth
(``num_hidden_layers``; every width, the vocabulary and the walks are whole);
documents packed into a row are not separated; ``beta`` constant at the
paper's first-stage value (the config has no key for it); the exit
distribution by products of sigmoids as written above, where a trainer would
work in logarithms (the program does): float32 holds both at these gates.

``precision="float8"`` is the control of the output check only: both operands
of every matmul (attention's q, k, v and the gate's among them) rounded to
e4m3.
"""

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference.afmoe_ref import _by_token_blocks, _rms, _swiglu, rotary  # noqa: F401
from benchmarks.reference.gpt_ref import _attention, _mm, adam_init, seed_key  # noqa: F401

KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size", "rms_norm_eps", "rope_theta", "vocab_size",
        "total_ut_steps", "entropy_beta")


def dims(config):
    """The sizes the reference needs, from a configuration file's keys."""
    d = {k: config[k] for k in KEYS}
    if d["num_key_value_heads"] != d["num_attention_heads"]:
        raise ValueError("this reference's attention has a key/value head a query head")
    d["vocab_rows"] = d["vocab_size"]
    return d


def make_weights(d, key, dtype=jnp.float32):
    """Random weights from ``key`` (``seed_key(seed)``): normal(0, 0.02),
    residual projections scaled by 1/sqrt(2 T L) (every layer adds to the
    stream T times); norm weights 1 + normal(0.1) so that every one is
    exercised; the gate's weight normal(0, 0.02), its bias 0."""
    H, L, T = d["hidden_size"], d["num_hidden_layers"], d["total_ut_steps"]
    A, I, V = d["num_attention_heads"] * d["head_dim"], d["intermediate_size"], d["vocab_rows"]
    k = iter(jax.random.split(key, 20))

    def n(shape, std):
        return (std * jax.random.normal(next(k), shape, jnp.float32)).astype(dtype)

    def unit(shape):
        return (1 + n(shape, 0.1).astype(jnp.float32)).astype(dtype)

    res = 0.02 / (2 * T * L) ** 0.5
    return {
        "embed": n((V, H), 0.02), "head": n((V, H), 0.02), "norm_f": unit((H,)),
        "norm1": unit((L, H)), "norm1_post": unit((L, H)),
        "norm2": unit((L, H)), "norm2_post": unit((L, H)),
        "attn": {"w_q": n((L, H, A), 0.02), "w_k": n((L, H, A), 0.02),
                 "w_v": n((L, H, A), 0.02), "w_o": n((L, A, H), res)},
        "mlp": {"w_gate": n((L, H, I), 0.02), "w_up": n((L, H, I), 0.02),
                "w_down": n((L, I, H), res)},
        "gate": {"w": n((H,), 0.02), "b": jnp.zeros((), dtype)},
    }


def block(lw, d, x, precision, q_block=256):
    """One block on ONE sequence x (S, H)."""
    S = x.shape[0]
    nh, dh, eps, theta = (d["num_attention_heads"], d["head_dim"], d["rms_norm_eps"],
                          d["rope_theta"])
    a = _rms(x, lw["norm1"], eps)
    q, k, v = (_mm("sh,hf->sf", a, lw["attn"][n], precision).reshape(S, nh, dh)
               for n in ("w_q", "w_k", "w_v"))
    ctx = _attention(rotary(q, theta), rotary(k, theta), v, q_block, precision)
    x = x + _rms(_mm("sf,fh->sh", ctx, lw["attn"]["w_o"], precision), lw["norm1_post"], eps)
    m = _rms(x, lw["norm2"], eps)
    y = _by_token_blocks(lambda m: _swiglu(m, lw["mlp"]["w_gate"], lw["mlp"]["w_up"],
                                           lw["mlp"]["w_down"], precision), m)
    return x + _rms(y, lw["norm2_post"], eps)


def exit_losses(head, x, targets, precision, token_block=2048):
    """Per-token cross-entropy (N,) of x (N, H) through the head, the logits
    ``token_block`` tokens at a time."""
    N = x.shape[0]
    block = min(token_block, N)

    @jax.checkpoint
    def some(args):
        xb, tgt = args
        lg = _mm("th,vh->tv", xb, head, precision)
        return jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(lg, tgt[..., None], -1)[..., 0]

    return jax.lax.map(some, (x.reshape(N // block, block, -1),
                              targets.reshape(N // block, block))).reshape(N)


def trip_states(w, d, tokens, *, precision="float32"):
    """``h^1 .. h^T`` (T, B, S, H) of a batch of token ids (B, S). A walk is
    recomputed whole in the backward pass and, inside it, every block of
    every row: what stands between the passes is ``h^t`` alone. The walks are
    a scan over the same weights and the rows go through a layer one after
    another INSIDE the scan over the layers, so a layer's gradient is summed
    over the rows and the walks where it is made: beside the accumulated
    gradient only one walk's stands whole."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    layers = {n: w[n] for n in ("norm1", "norm1_post", "norm2", "norm2_post", "attn", "mlp")}

    def one(x, lw):
        return jax.lax.map(jax.checkpoint(lambda row: block(lw, d, row, precision)), x), None

    @jax.checkpoint
    def walk(x, _):
        x = _rms(jax.lax.scan(one, x, layers)[0], w["norm_f"], d["rms_norm_eps"])
        return x, x

    return jax.lax.scan(walk, w["embed"][tokens], None, length=d["total_ut_steps"])[1]


def exit_distribution(gate, states, precision):
    """``p`` (T, N) from ``h^1 .. h^(T-1)`` (T - 1, N, H): ``g^t = sigmoid(h^t .
    w + b)``, ``p^t = g^t prod_{j<t} (1 - g^j)``, the last what is left."""
    g = jax.nn.sigmoid(_mm("tnh,h->tn", states, gate["w"], precision) + gate["b"])
    left = jnp.cumprod(1 - g, axis=0)                    # prod_{j<=t} (1 - g^j)
    return jnp.concatenate([g[:1], g[1:] * left[:-1], left[-1:]])


def loss(w, d, tokens, targets, *, precision="float32"):
    """(the objective, {``exit_losses`` (T,) the mean of ``l^t``, ``exit_mass``
    (T,) the mean of ``p^t``, ``exit_entropy`` ()}) over a batch (B, S)."""
    B, S = tokens.shape
    states = trip_states(w, d, tokens, precision=precision).reshape(
        d["total_ut_steps"], B * S, -1)
    head = w["head"].astype(jnp.float32)
    l = jnp.stack([exit_losses(head, x, targets.reshape(-1), precision) for x in states])
    gate = jax.tree.map(lambda a: a.astype(jnp.float32), w["gate"])
    p = exit_distribution(gate, states[:-1], precision)
    plogp = jnp.sum(p * jnp.log(p), axis=0)
    value = jnp.mean(jnp.sum(p * l, axis=0) + d["entropy_beta"] * plogp)
    return value, {"exit_losses": jnp.mean(l, axis=1), "exit_mass": jnp.mean(p, axis=1),
                   "exit_entropy": -jnp.mean(plogp)}


def train_step(w, opt, d, tokens, targets, *, lr, b1=0.9, b2=0.999, eps=1e-8,
               precision="float32"):
    """One step of plain Adam, as ``gpt_ref.train_step`` does it. Returns
    (weights, state, loss, gradients, the exits' readings)."""
    (value, exits), g = jax.value_and_grad(functools.partial(
        loss, precision=precision), has_aux=True)(w, d, tokens, targets)
    t = opt["t"] + 1
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["m"], g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["v"], g)
    w = jax.tree.map(
        lambda p, m, v: p - lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps),
        w, m, v)
    return w, {"m": m, "v": v, "t": t}, value, g, exits
