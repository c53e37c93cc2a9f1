"""The chunked state-space scan (``ops.ssd.ssd_scan``) against the recurrence
it stands for, token by token: value and every cotangent (``dA``, ``dD`` and
``ddt`` among them) over several chunks and a ragged last one, heads that
share a lane tile and heads that fill one, groups that share ``B`` and ``C``,
bf16 operands, the XLA twin, and the shape rule with its refusals."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu.ops import ssd  # noqa: E402
from apex_tpu.ops.pallas import ssd as kernels  # noqa: E402
from comparisons import gap, kernel_calls  # noqa: E402

NAMES = ("x", "dt", "A", "B", "C", "D")


def recurrence(x, dt, A, B, C, D):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T``, ``y_t = S_t^T C_t + D
    x_t`` a head with its group's ``B``, ``C``: the ground truth, float32."""
    b, t, h, p = x.shape
    g, n = B.shape[2:]
    per_head = lambda a: jnp.repeat(a.astype(jnp.float32), h // g, axis=2)  # noqa: E731

    def token(S, inputs):
        x_t, dt_t, b_t, c_t = inputs
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * b_t)[..., None] * x_t[..., None, :])
        return S, jnp.einsum("bhnp,bhn->bhp", S, c_t) + D[:, None] * x_t

    inputs = tuple(jnp.moveaxis(a, 1, 0) for a in (
        x.astype(jnp.float32), dt, per_head(B), per_head(C)))
    _, y = jax.lax.scan(token, jnp.zeros((b, h, n, p)), inputs)
    return jnp.moveaxis(y, 0, 1)


def operands(b, t, h, p, g, n, dtype=jnp.float32, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(k[0], (b, t, h, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, t, h)) - 2.0)
    A = -jax.random.uniform(k[2], (h,), minval=1.0, maxval=16.0)
    B, C = (0.3 * jax.random.normal(k[i], (b, t, g, n)).astype(dtype) for i in (3, 4))
    D = 1.0 + 0.1 * jax.random.normal(k[5], (h,))
    return (x, dt, A, B, C, D), jax.random.normal(k[6], (b, t, h, p))


def value_and_grads(fn, args, ct):
    def loss(*a):
        y = fn(*a)
        return jnp.sum(y.astype(jnp.float32) * ct), y
    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(6)), has_aux=True))(*args)
    return y, grads


# (b, t, heads, P, groups, N): three chunks with a ragged last one, two heads
# a lane tile; ten chunks (two grid steps, padded to sixteen), a head a tile
SHAPES = [(1, 300, 4, 64, 2, 128), (2, 1200, 2, 128, 1, 128)]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("shape", SHAPES, ids=["ragged-heads-of-64", "two-steps-heads-of-128"])
def test_value_and_every_cotangent_match_the_recurrence(shape, impl):
    args, ct = operands(*shape)
    want, g_want = value_and_grads(recurrence, args, ct)
    got, g_got = value_and_grads(lambda *a: ssd.ssd_scan(*a, impl=impl), args, ct)
    assert gap(got, want) <= 1e-4, "y"
    for name, a, b in zip(NAMES, g_got, g_want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert gap(a, b) <= 1e-4, "d" + name


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_bf16_operands_stay_near_the_float32_recurrence(impl):
    args, ct = operands(1, 384, 4, 64, 2, 128, jnp.bfloat16, seed=3)
    want, g_want = value_and_grads(recurrence, args, ct)
    got, g_got = value_and_grads(lambda *a: ssd.ssd_scan(*a, impl=impl), args, ct)
    assert got.dtype == jnp.bfloat16 and g_got[0].dtype == jnp.bfloat16
    assert g_got[1].dtype == g_got[2].dtype == g_got[5].dtype == jnp.float32
    assert gap(got, want) <= 2e-2, "y"
    for name, a, b in zip(NAMES, g_got, g_want):
        assert gap(a, b.astype(jnp.float32)) <= 3e-2, "d" + name


def test_the_kernels_and_the_xla_twin_agree_in_bf16():
    """Both round the same operands to bf16 at the same places: closer to
    each other than either is to the float32 recurrence."""
    args, ct = operands(2, 256, 4, 64, 2, 128, jnp.bfloat16, seed=5)
    one, g_one = value_and_grads(lambda *a: ssd.ssd_scan(*a, impl="pallas"), args, ct)
    two, g_two = value_and_grads(lambda *a: ssd.ssd_scan(*a, impl="xla"), args, ct)
    assert gap(one, two.astype(jnp.float32)) <= 1e-2, "y"
    for name, a, b in zip(NAMES, g_one, g_two):
        assert gap(a, b.astype(jnp.float32)) <= 2e-2, "d" + name


def test_a_token_with_no_time_step_neither_decays_nor_writes():
    """What the padding relies on: ``dt = 0`` leaves the state as it was."""
    (x, dt, A, B, C, D), _ = operands(1, 256, 2, 64, 1, 128)
    dt = dt.at[:, 100:140].set(0.0)
    y = ssd.ssd_scan(x, dt, A, B, C, D, impl="pallas")
    keep = np.r_[0:100, 140:256]
    skipped = ssd.ssd_scan(x[:, keep], dt[:, keep], A, B[:, keep], C[:, keep], D, impl="pallas")
    assert gap(y[:, keep], skipped) <= 1e-5, "y around the still tokens"


@pytest.mark.parametrize("case,ok", [
    ((64, 128, 64, 8, 128), True), ((128, 128, 8, 1, 128), True), ((32, 256, 8, 2, 256), True),
    ((64, 128, 64, 8, 64), False),      # a chunk's matrices in whole lane tiles
    ((64, 64, 64, 8, 128), False),      # the state's rows a lane block of B and C
    ((96, 128, 4, 1, 128), False),      # a head an exact share of a lane tile
    ((64, 128, 6, 6, 128), False),      # a group's heads in whole lane tiles
    ((64, 128, 6, 4, 128), False),      # heads a multiple of groups
])
def test_the_shape_rule(case, ok):
    P, N, heads, groups, chunk = case
    assert ssd.shapes_ok(P, N, heads, groups, chunk) is ok


def test_pallas_refuses_what_the_rule_refuses_and_auto_falls_back():
    args, _ = operands(1, 64, 4, 16, 2, 16)
    with pytest.raises(ValueError, match="tiling"):
        ssd.ssd_scan(*args, chunk=16, impl="pallas")
    with pytest.raises(ValueError, match="impl"):
        ssd.ssd_scan(*args, chunk=16, impl="mosaic")
    y = ssd.ssd_scan(*args, chunk=16, impl="auto")          # the XLA form
    assert gap(y, recurrence(*args)) <= 1e-4, "y"


def test_the_kernels_carry_their_names_and_the_states_are_the_steps():
    (x, dt, A, B, C, D), _ = operands(1, 2048, 2, 64, 1, 128)
    grad = jax.make_jaxpr(jax.grad(lambda x: jnp.sum(
        ssd.ssd_scan(x, dt, A, B, C, D, impl="pallas"))))(x)
    assert kernel_calls(grad.jaxpr) == {"ssd_fwd": 1, "ssd_bwd": 1}
    flat = lambda a: a.reshape(1, 2048, -1)  # noqa: E731
    _, s0 = kernels.ssd_fwd(flat(x), dt, A, flat(B), flat(C), D, groups=1, chunk=128,
                            interpret=True)
    # sixteen chunks in two steps of eight: one entry state a step, a lane
    # tile of two heads, float32 — never one a token
    assert s0.shape == (1, 1, 2, 1, 128, 128) and s0.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(s0[0, 0, 0]))) == 0.0 and float(jnp.max(jnp.abs(s0[0, 0, 1]))) > 0
