"""The chunked gated delta rule (XLA form and the ``gdn_fwd`` / ``gdn_bwd``
kernels, which build every chunk's operands in VMEM) against the
token-by-token recurrence of the plain reference and against each other,
value and all five gradients; a batch on the kernels (two rows a grid step)
against the same kernels a row at a time, bit for bit; and the small ops
around it."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu.ops.gated_delta_rule import (causal_conv_silu, gated_delta_rule,  # noqa: E402
                                           gated_rms_norm, _unit_lower_inverse)
from apex_tpu.ops.pallas import gated_delta_rule as kernels  # noqa: E402
from apex_tpu.ops.rotary import apply_partial_rotary  # noqa: E402
from benchmarks.reference import hybrid_ref as R  # noqa: E402
from comparisons import BATCH_AND_CHUNKS, batch_equals_its_rows  # noqa: E402


def recurrence(q, k, v, g, beta):
    hk, hv, dk = q.shape[2], v.shape[2], q.shape[-1]
    qn = jnp.repeat(R._l2(q) / dk ** 0.5, hv // hk, axis=2)
    kn = jnp.repeat(R._l2(k), hv // hk, axis=2)
    return jax.vmap(lambda *a: R.delta_rule_recurrence(*a, time_block=32))(qn, kn, v, g, beta)


def inputs(t, decay, b=2, hk=1, hv=2, d=128, seed=0, drift=0.0, sign=1.0):
    """``drift`` > 0: keys as training leaves them — a share ``drift`` of every
    key is one common direction (times ``sign`` on odd tokens: -1 makes
    neighbours point against each other), and the writes are strong (beta
    near 1): the chunk's triangular system is then far from the identity."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    lo, hi = {"mixed": (-7.0, 1.5), "near_one": (-12.0, -9.0), "near_zero": (2.0, 3.0)}[decay]
    odd = jnp.where(jnp.arange(t) % 2 == 1, sign, 1.0)[None, :, None, None]
    k = ((1 - drift) * jax.random.normal(ks[1], (b, t, hk, d))
         + drift * odd * jax.random.normal(ks[6], (b, 1, hk, d)))
    return (jax.random.normal(ks[0], (b, t, hk, d)), k,
            jax.random.normal(ks[2], (b, t, hv, d)),
            -jnp.exp(jax.random.uniform(ks[3], (b, t, hv), minval=lo, maxval=hi)),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, hv)) + (4.0 if drift else 0.0)),
            jax.random.normal(ks[5], (b, t, hv, d)))


def value_and_grads(f, x, do):
    return jax.jit(jax.value_and_grad(lambda *a: jnp.sum(f(*a).astype(jnp.float32) * do),
                                      argnums=range(5)))(*x)


def check(impl, t, decay, tol=2e-5, chunk=64, **keys):
    """Value and the gradients of q, k, v, g, beta against the recurrence."""
    *x, do = inputs(t, decay, **keys)
    rule = lambda *a: gated_delta_rule(*a, chunk=chunk, impl=impl)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want, want_g = value_and_grads(recurrence, x, do)
        got, got_g = value_and_grads(rule, x, do)
        o, o_ref = jax.jit(rule)(*x), jax.jit(recurrence)(*x)
    assert o.shape == o_ref.shape
    np.testing.assert_allclose(o, o_ref, atol=tol * float(jnp.max(jnp.abs(o_ref))))
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for a, r in zip(got_g, want_g):
        np.testing.assert_allclose(a, r, atol=tol * float(jnp.max(jnp.abs(r))) + 1e-7)


@pytest.mark.parametrize("t,decay", [
    (128, "mixed"),        # two whole chunks
    (150, "mixed"),        # a sequence that is not a multiple of the chunk
    (96, "near_one"),      # exp(g) ~ 1 - 1e-5: the state never forgets
    (96, "near_zero"),     # exp(g) ~ e^-10: the state is gone within a token
])
def test_chunked_xla_form_matches_the_recurrence(t, decay):
    check("xla", t, decay)


@pytest.mark.parametrize("t,decay,chunk,heads", [
    (150, "mixed", 64, (1, 2)),       # three chunks, the last one padded; two value heads a key head
    (96, "near_one", 64, (1, 2)),
    (96, "near_zero", 64, (1, 2)),
    (150, "mixed", 16, (1, 2)),       # ten chunks pad to sixteen: two grid steps, state and dS carried over
    (300, "near_one", 16, (2, 4)),    # three grid steps, two key heads (a grid row each)
    (130, "mixed", 32, (2, 2)),       # a value head a key head; the substitution alone (no merge)
])
def test_kernels_match_the_recurrence(t, decay, chunk, heads):
    check("pallas", t, decay, chunk=chunk, hk=heads[0], hv=heads[1])


@pytest.mark.parametrize("t,decay,chunk", [(150, "mixed", 64), (96, "near_one", 64),
                                           (96, "near_zero", 64), (200, "mixed", 16)])
def test_kernels_agree_with_the_xla_form(t, decay, chunk):
    """Value and all five gradients, float32: the kernels build the operands
    the XLA form builds."""
    *x, do = inputs(t, decay, hk=2, hv=4)
    with jax.default_matmul_precision("highest"):
        (a, ga), (b, gb) = (value_and_grads(
            lambda *y: gated_delta_rule(*y, chunk=chunk, impl=impl), x, do) for impl in ("pallas", "xla"))
    np.testing.assert_allclose(a, b, rtol=1e-5)
    for m, n in zip(ga, gb):
        np.testing.assert_allclose(m, n, atol=1e-5 * float(jnp.max(jnp.abs(n))) + 1e-7)


def kernel_operands(b, n, C=16, d=128):
    """What ``gdn_fwd`` / ``gdn_bwd`` take, bf16 as the cells run them: ``b`` rows of
    ``n`` chunks of ``C`` tokens, one key head and its value head, and ``do``."""
    ks = jax.random.split(jax.random.PRNGKey(b * 100 + n), 6)
    q, k, v, do = (jax.random.normal(ks[i], (b, n * C, d), jnp.bfloat16) for i in range(4))
    G = jnp.cumsum(-jnp.exp(jax.random.uniform(ks[4], (b, 1, n, C), minval=-7.0, maxval=0.5)), -1)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (b, 1, n, C)))
    return (q, k, v, G, beta, jnp.broadcast_to(G[..., -1:], G.shape[:-1] + (d,))), do


@jax.jit
def kernel_pair(q, k, v, G, beta, gl, do):
    o, s0 = kernels.gdn_fwd(q, k, v, G, beta, gl, heads=1, interpret=True)
    return (o, s0) + tuple(kernels.gdn_bwd(q, k, v, G, beta, gl, s0, do, heads=1, interpret=True))


@pytest.mark.parametrize("b,n", BATCH_AND_CHUNKS)
def test_a_batch_on_the_kernels_equals_its_rows_bit_for_bit(b, n):
    """``o``, the states the blocks started from and all six cotangents."""
    batch_equals_its_rows(kernel_pair, *kernel_operands(b, n))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("drift,sign", [(0.5, 1.0), (0.5, -1.0), (0.9, 1.0), (0.9, -1.0)])
def test_chunked_form_holds_on_drifted_keys(drift, sign, impl):
    """Keys with a cosine of 0.5 to 0.99 between any two (of either sign),
    strong writes, slow decay: ``I + A`` has entries near +-1 everywhere
    below the diagonal. (The inverse as a product of powers of ``A`` was 1e7
    to 1e30 off here, and a training run at lr 3e-4 reached such keys within
    26 steps.) The kernels invert in VMEM by the same substitution and
    merges."""
    check(impl, 192, "near_one", tol=5e-5, drift=drift, sign=sign)


@pytest.mark.slow
@pytest.mark.parametrize("t,decay", [(640, "mixed"), (576, "near_one"), (128, "near_zero")])
def test_kernels_match_the_recurrence_over_several_blocks(t, decay):
    """640 tokens pad to 16 chunks of 64: two grid steps of eight a row, the
    state and its cotangent carried between them."""
    check("pallas", t, decay)


@pytest.mark.parametrize("decay,drift", [("mixed", 0.0), ("near_one", 0.7)])
def test_kernel_and_xla_forms_agree_in_bfloat16(decay, drift):
    """bf16 operands on both paths (the scores, ``T``'s products and the
    recurrence round at the same places), all five gradients."""
    *x, do = inputs(192, decay, drift=drift)
    x = [a.astype(jnp.bfloat16) for a in x[:3]] + x[3:]
    (a, ga), (b, gb) = (value_and_grads(lambda *y: gated_delta_rule(*y, impl=impl), x, do)
                        for impl in ("pallas", "xla"))
    np.testing.assert_allclose(a, b, rtol=2e-2)
    for m, n in zip(ga, gb):
        gap = jnp.abs(m.astype(jnp.float32) - n.astype(jnp.float32))
        assert float(jnp.max(gap)) <= 0.03 * float(jnp.max(jnp.abs(n.astype(jnp.float32))))


@pytest.mark.parametrize("size,fill,sign", [(64, 0.98, 1), (64, 0.98, -1), (48, 0.9, 1),
                                            (16, 0.98, -1), (10, 0.9, 1)])
def test_unit_lower_inverse_of_a_matrix_far_from_the_identity(size, fill, sign):
    """All entries below the diagonal near 1 (every key the same) or near
    (-1)^(i-j) (neighbours opposed): the inverse is bidiagonal to within
    1 - fill, its entries at most 1."""
    noise = 0.02 * jax.random.normal(jax.random.PRNGKey(4), (2, size, size))
    i = jnp.arange(size)
    a = jnp.tril((fill + noise) * jnp.where((i[:, None] - i[None, :]) % 2 == 1, sign, 1), -1)
    want = np.linalg.inv(np.eye(size) + np.asarray(a, np.float64))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(_unit_lower_inverse(a), want, atol=2e-5)


def test_unit_lower_inverse_and_its_gradient():
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(1), (3, 64, 64)) * 0.3, -1)
    eye = jnp.eye(64)
    with jax.default_matmul_precision("highest"):
        t = _unit_lower_inverse(a)
        np.testing.assert_allclose(jnp.matmul(eye + a, t), jnp.broadcast_to(eye, a.shape), atol=2e-4)
        r = jax.random.normal(jax.random.PRNGKey(2), a.shape)
        got = jax.jit(jax.grad(lambda a: jnp.sum(_unit_lower_inverse(a) * r)))(a)
        want = jax.jit(jax.grad(lambda a: jnp.sum(jnp.linalg.inv(eye + a) * r)))(a)
    np.testing.assert_allclose(got, want, atol=1e-3 * float(jnp.max(jnp.abs(want))))


def test_conv_norm_and_rotary_match_the_reference():
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    x, w = jax.random.normal(k[0], (2, 40, 24)), jax.random.uniform(k[1], (4, 24), minval=-.5, maxval=.5)
    np.testing.assert_allclose(causal_conv_silu(x, w),
                               jax.vmap(lambda r: R.causal_conv_silu(r, w))(x), atol=1e-6)
    assert float(jnp.max(jnp.abs(causal_conv_silu(x.at[:, 20:].set(0), w)[:, :20]
                                 - causal_conv_silu(x, w)[:, :20]))) == 0.0   # causal
    o, z, g = jax.random.normal(k[2], (2, 5, 3, 16)), jax.random.normal(k[3], (2, 5, 3, 16)), jnp.ones(16) * 1.3
    want = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-6) * g * jax.nn.silu(z)
    np.testing.assert_allclose(gated_rms_norm(o, z, g), want, rtol=1e-5, atol=1e-6)
    h = jax.random.normal(k[4], (2, 33, 3, 32))
    d = {"rotary_dim": 8, "rope_theta": 1e7}
    np.testing.assert_allclose(apply_partial_rotary(h, 8, 1e7),
                               jax.vmap(lambda r: R.rotary(r, d))(h), atol=1e-5)
    np.testing.assert_array_equal(apply_partial_rotary(h, 8, 1e7)[..., 8:], h[..., 8:])
