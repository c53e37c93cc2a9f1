"""The delta rule whose decay is a vector a key channel (``ops.gated_delta_rule
.kda_rule``) against the recurrence it stands for, token by token: value and
every cotangent (the per-channel log decay's among them) over several chunks,
a ragged last one and more than one grid step, decays AT the kernels' bound
for a whole chunk and near 0, a decay that is constant over a head's channels
equal to ``gated_delta_rule`` on the same inputs, bf16 operands, the XLA twin,
a batch on the kernels (two rows a grid step) against the same kernels a row at
a time, the names ``remat`` keeps and the shape rule."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu.ops import gated_delta_rule as gdr  # noqa: E402
from apex_tpu.ops.pallas import kda as kernels  # noqa: E402
from comparisons import (BATCH_AND_CHUNKS, batch_equals_its_rows, gap,  # noqa: E402
                         kernel_calls)

NAMES = ("q", "k", "v", "g", "beta")


def recurrence(q, k, v, g, beta):
    """``S <- Diag(exp(g_t)) S; u_t = beta_t (v_t - S^T k_t); S <- S + k_t
    u_t^T; o_t = S^T q_t`` a head, float32: the ground truth, no chunks."""
    b, t, h, dk = q.shape
    qn = gdr.l2_normalize(q) * dk ** -0.5
    kn = gdr.l2_normalize(k)

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t, precision="highest"))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision="highest")

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (qn, kn, v.astype(jnp.float32), g, beta))
    _, o = jax.lax.scan(token, jnp.zeros((b, h, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def operands(b, t, h, d, dtype=jnp.float32, seed=0, decay="spread"):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, key, v = (jax.random.normal(k[i], (b, t, h, d)).astype(dtype) for i in range(3))
    g = kernels.LOG_DECAY_MIN * jax.nn.sigmoid(3.0 * jax.random.normal(k[3], (b, t, h, d)))
    if decay == "bound":            # the second chunk whole AT the bound, every channel
        g = g.at[:, 64:128].set(kernels.LOG_DECAY_MIN)
    if decay == "none":             # a state that hardly forgets
        g = jnp.full_like(g, -1e-4)
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (b, t, h)))
    return (q, key, v, g, beta), jax.random.normal(k[5], (b, t, h, d))


def value_and_grads(fn, args, ct):
    def loss(*a):
        y = fn(*a)
        return jnp.sum(y.astype(jnp.float32) * ct), y
    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(5)), has_aux=True))(*args)
    return y, grads


def rule(impl):
    return lambda *a: gdr.kda_rule(*a, impl=impl)


# (b, t, heads, d): four chunks with a ragged last one in one grid step (the backward's
# loop over the chunks two bodies of two); eighteen chunks padded to three grid steps of
# eight, the state carried between them (``BACK`` = 8: one body a step); six chunks:
# three bodies of two; five: five bodies of one
SHAPES = [(1, 200, 2, 128), (2, 1100, 1, 128), (1, 384, 2, 128), (1, 320, 1, 128)]


@pytest.mark.parametrize("shape,impl", [(SHAPES[0], "xla"), (SHAPES[0], "pallas"),
                                        (SHAPES[1], "pallas"), (SHAPES[2], "pallas"),
                                        (SHAPES[3], "pallas")],
                         ids=["ragged-one-step-xla", "ragged-one-step-pallas", "three-steps-pallas",
                              "six-chunks-pallas", "five-chunks-pallas"])
def test_value_and_every_cotangent_match_the_recurrence(shape, impl):
    args, ct = operands(*shape)
    want_y, want = value_and_grads(recurrence, args, ct)
    got_y, got = value_and_grads(rule(impl), args, ct)
    assert gap(got_y, want_y) < 2e-5
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape and gap(a, b) < 2e-4, name


@pytest.mark.parametrize("decay,impl", [("bound", "xla"), ("bound", "pallas"), ("none", "pallas")])
def test_decays_at_the_bound_and_near_zero(decay, impl):
    """A whole chunk at -5 a step in every channel: the sub-chunks' factors
    reach e^75 and their partners e^-75, inside float32; the cumulative decay
    e^-320 underflows to the 0 it stands for. And a decay of e^-1e-4: a state
    that keeps what 200 tokens wrote."""
    args, ct = operands(1, 200, 2, 128, seed=3, decay=decay)
    want_y, want = value_and_grads(recurrence, args, ct)
    got_y, got = value_and_grads(rule(impl), args, ct)
    assert bool(jnp.isfinite(got_y).all()) and gap(got_y, want_y) < 2e-5
    for name, a, b in zip(NAMES, got, want):
        assert bool(jnp.isfinite(a).all()) and gap(a, b) < 3e-4, (name, decay)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_decay_constant_over_the_channels_is_the_scalar_rule(impl):
    """``g_t`` the same in all of a head's channels: ``gated_delta_rule`` on
    the same inputs, value and every cotangent (the vector decay's summed over
    the channels is the scalar's)."""
    (q, k, v, g, beta), ct = operands(1, 200, 2, 128, seed=5)
    scalar = g[..., 0]
    wide = lambda s: jnp.broadcast_to(s[..., None], g.shape)  # noqa: E731
    want_y, want = value_and_grads(
        lambda q, k, v, s, beta: gdr.gated_delta_rule(q, k, v, s, beta, impl="xla"),
        (q, k, v, scalar, beta), ct)
    got_y, got = value_and_grads(
        lambda q, k, v, s, beta: gdr.kda_rule(q, k, v, wide(s), beta, impl=impl),
        (q, k, v, scalar, beta), ct)
    assert gap(got_y, want_y) < 2e-5
    for name, a, b in zip(NAMES, got, want):
        assert gap(a, b) < 2e-4, name


@pytest.mark.parametrize("impl", ["pallas"])
def test_bf16_operands_keep_decay_and_state_float32(impl):
    args, ct = operands(1, 200, 2, 128, dtype=jnp.bfloat16, seed=7)
    as32 = tuple(a.astype(jnp.float32) for a in args)
    want_y, want = value_and_grads(recurrence, as32, ct)
    got_y, got = value_and_grads(rule(impl), args, ct)
    assert got_y.dtype == jnp.bfloat16 and got[0].dtype == jnp.bfloat16
    assert got[3].dtype == jnp.float32 and got[4].dtype == jnp.float32
    assert gap(got_y.astype(jnp.float32), want_y) < 3e-2
    for name, a, b in zip(NAMES, got, want):
        assert gap(a.astype(jnp.float32), b) < 5e-2, name


def kernel_operands(b, n, C=16, d=128):
    """What ``kda_fwd`` / ``kda_bwd`` take, bf16 as the cell runs them: ``b`` rows of
    ``n`` chunks of ``C`` tokens, one head, decays over the whole of (-5, 0), and ``do``."""
    ks = jax.random.split(jax.random.PRNGKey(b * 100 + n), 6)
    q, k, v, do = (jax.random.normal(ks[i], (b, n * C, d), jnp.bfloat16) for i in range(4))
    g = kernels.LOG_DECAY_MIN * jax.nn.sigmoid(4.0 * jax.random.normal(ks[4], (b, n * C, d)))
    return (q, k, v, g, jax.nn.sigmoid(jax.random.normal(ks[5], (b, 1, n, C)))), do


@jax.jit
def kernel_pair(q, k, v, g, beta, do):
    o, s0 = kernels.kda_fwd(q, k, v, g, beta, interpret=True)
    return (o, s0) + tuple(kernels.kda_bwd(q, k, v, g, beta, s0, do, interpret=True))


@pytest.mark.parametrize("b,n", BATCH_AND_CHUNKS)
def test_a_batch_on_the_kernels_equals_its_rows_bit_for_bit(b, n):
    """``o``, the states the blocks started from and all five cotangents."""
    batch_equals_its_rows(kernel_pair, *kernel_operands(b, n))


def test_remat_keeps_the_kernels_results_by_name():
    """Under a policy that keeps ``KDA_SAVED`` a recomputed call launches
    ``kda_fwd`` once: the backward rule reads the saved output and entry
    states; without the names it runs twice."""
    args, ct = operands(1, 128, 1, 128)

    def calls(saved):
        f = jax.checkpoint(rule("pallas"),
                           policy=jax.checkpoint_policies.save_only_these_names(*saved))
        jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(f(*a) * ct), argnums=(0, 3)))(*args)
        return kernel_calls(jaxpr.jaxpr)

    assert gdr.KDA_SAVED == ("kda_o", "kda_s0")
    assert calls(gdr.KDA_SAVED) == {"kda_fwd": 1, "kda_bwd": 1}
    assert calls(()) == {"kda_fwd": 2, "kda_bwd": 1}


def test_shape_rule_and_the_bound():
    assert gdr.kda_shapes_ok(128, 128, 64) and gdr.kda_shapes_ok(256, 128, 32)
    assert not gdr.kda_shapes_ok(64, 128, 64) and not gdr.kda_shapes_ok(128, 128, 24)
    # the bound the kernels' float32 factors need: a sub-chunk's steps less one, inside e^88
    assert (kernels.SUB - 1) * -kernels.LOG_DECAY_MIN < 88
    assert gdr.KDA_LOG_DECAY_MIN == kernels.LOG_DECAY_MIN == -5.0
    # a head too narrow for the kernels takes the XLA form under ``auto``
    args, ct = operands(1, 70, 2, 32)
    got_y, _ = value_and_grads(rule("auto"), args, ct)
    assert gap(got_y, recurrence(*args)) < 2e-5
