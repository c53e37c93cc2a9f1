"""The kernels of the two stages around the gated delta rule
(``conv_silu_fwd`` / ``conv_silu_bwd``, ``gated_norm_fwd`` / ``gated_norm_bwd``)
against the XLA compositions they stand in for: values and every gradient,
float32 and bfloat16, halos across time blocks and strips, inputs read in
place from a wider array, and the shapes the kernels refuse."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu.models import HybridDecoderConfig, HybridDecoderModel  # noqa: E402
from apex_tpu.ops.gated_delta_rule import (causal_conv_silu, conv_shapes_ok,  # noqa: E402
                                           gated_rms_norm, norm_shapes_ok)
from apex_tpu.ops.pallas import delta_mixer as M  # noqa: E402

F32 = jnp.float32
# relative to the largest entry of what is compared: float32 sums in another
# order; one unit in the last place of a bfloat16 (2^-7 of its binade)
TOL = {jnp.float32: 2e-6, jnp.bfloat16: 1e-2}


def close(got, want, dtype, what=""):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, atol=TOL[dtype] * float(np.max(np.abs(want))) + 1e-7,
                               rtol=0, err_msg=what)


def conv_case(dtype, t, channels, extra, taps=4, b=2, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(k[0], (b, t, channels + extra)).astype(dtype)
    w = jax.random.uniform(k[1], (taps, channels), minval=-0.5, maxval=0.5).astype(dtype)
    dy = jax.random.normal(k[2], (b, t, channels))
    return x, w, dy


def conv_value_and_grads(impl, x, w, dy, widths):
    def f(x, w):
        ys = causal_conv_silu(x, w, widths=widths, impl=impl)
        y = jnp.concatenate(ys, -1) if widths else ys
        return jnp.sum(y.astype(F32) * dy), y
    (_, y), g = jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(x, w)
    return (y,) + g


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,channels,extra,widths", [
    (2112, 128, 0, None),             # two time blocks of 1056 rows (float32: three of 704)
    (96, 512, 256, (128, 128, 256)),  # pieces read in place from a wider array, one block
    (4224, 384, 128, (256, 128)),     # three blocks of 1408 rows (six of 704), 256- and 128-lane pieces
    (48, 128, 0, None),               # a block of 48 rows: strips of 16
])
def test_conv_kernels_match_the_xla_composition(dtype, t, channels, extra, widths):
    """Value, ``dx`` (zeros in the channels the convolution does not read)
    and the taps' gradient."""
    x, w, dy = conv_case(dtype, t, channels, extra)
    assert conv_shapes_ok(x, w, widths or (channels,))
    got = conv_value_and_grads("pallas", x, w, dy, widths)
    want = conv_value_and_grads("xla", x, w, dy, widths)
    for a, b, what in zip(got, want, ("y", "dx", "dw")):
        assert a.shape == b.shape and a.dtype == b.dtype
        close(a, b, dtype, what)
    assert not np.any(np.asarray(got[1][..., channels:], np.float32))


@pytest.mark.parametrize("taps", [2, 3, 4, 7])
def test_conv_kernels_at_other_tap_counts(taps):
    x, w, dy = conv_case(jnp.float32, 160, 128, 0, taps=taps)
    for a, b in zip(conv_value_and_grads("pallas", x, w, dy, None),
                    conv_value_and_grads("xla", x, w, dy, None)):
        close(a, b, jnp.float32)


def test_bfloat16_conv_gradient_is_no_further_from_float32_than_xlas():
    """The XLA backward sums four bf16-rounded pieces; the kernel sums in
    float32 and rounds once."""
    x, w, dy = conv_case(jnp.bfloat16, 1088, 256, 0)
    exact = conv_value_and_grads("xla", x.astype(F32), w.astype(F32), dy, None)[1]
    gap = lambda impl: float(jnp.mean(jnp.abs(  # noqa: E731
        conv_value_and_grads(impl, x, w, dy, None)[1].astype(F32) - exact)))
    assert gap("pallas") <= gap("xla")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv_halo_crosses_block_edges_both_ways_and_rows_start_from_zeros(dtype):
    """A token's output depends on the three before it and on nothing else:
    not on a later token, not on the row before — also where a time block
    (1056 rows in bfloat16, 704 in float32) or a strip (32) starts; its cotangent reaches the three tokens
    before it across the same edges."""
    x, w, dy = conv_case(dtype, 2112, 128, 0)
    y = causal_conv_silu(x, w, impl="pallas")
    alone = causal_conv_silu(x[1:], w, impl="pallas")
    np.testing.assert_array_equal(np.asarray(y[1:], np.float32), np.asarray(alone, np.float32))
    for edge in (704, 1056, 1408, 32, 2111):
        later = causal_conv_silu(x.at[:, edge:].set(0), w, impl="pallas")
        np.testing.assert_array_equal(np.asarray(later[:, :edge], np.float32),
                                      np.asarray(y[:, :edge], np.float32))
        moved = causal_conv_silu(x.at[0, edge - 1].add(1.0), w, impl="pallas")
        rows = np.flatnonzero(np.any(np.asarray(moved[0] != y[0]), axis=-1))
        assert rows.min() == edge - 1 and rows.max() == min(edge + 2, 2111)
        # the cotangent of one output row lands on that row and the three before
        one = jnp.zeros_like(dy).at[0, edge].set(1.0) if edge < 2111 else jnp.zeros_like(dy).at[0, 0].set(1.0)
        dx = jax.jit(jax.grad(lambda x: jnp.sum(
            causal_conv_silu(x, w, impl="pallas").astype(F32) * one)))(x)
        rows = np.flatnonzero(np.any(np.asarray(dx[0], np.float32) != 0, axis=-1))
        assert (rows.min(), rows.max()) == ((edge - 3, edge) if edge < 2111 else (0, 0))
        assert not np.any(np.asarray(dx[1], np.float32))


@pytest.mark.parametrize("t,channels,ok", [
    (150, 128, False),      # rows outside whole sublane tiles
    (96, 24, False),        # channels outside whole lanes
    (2128, 128, True),      # no whole block of 1024: a divisor (304 rows) is the block
])
def test_shapes_the_conv_kernels_refuse_take_the_xla_path(t, channels, ok):
    x, w, dy = conv_case(jnp.float32, t, channels, 0)
    assert conv_shapes_ok(x, w, (channels,)) is ok
    want = conv_value_and_grads("xla", x, w, dy, None)
    for a, b in zip(conv_value_and_grads("auto", x, w, dy, None), want):
        close(a, b, jnp.float32)
    if not ok:
        with pytest.raises(ValueError, match="tiling"):
            causal_conv_silu(x, w, impl="pallas")
    assert conv_shapes_ok(x.astype(jnp.bfloat16), w, (channels,)) is (ok and t % 16 == 0)
    assert not conv_shapes_ok(x, jnp.zeros((M.HALO + 2, channels)), (channels,))


def norm_case(dtype, rows, heads, dim, extra, seed=1):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    o = jax.random.normal(k[0], rows + (heads, dim)).astype(dtype)
    z = jax.random.normal(k[1], rows + (extra + heads * dim,)).astype(dtype)
    w = (1.0 + 0.2 * jax.random.normal(k[2], (dim,))).astype(dtype)
    return o, z, w, jax.random.normal(k[3], o.shape)


def norm_value_and_grads(impl, o, z, w, dy):
    def f(o, z, w):
        y = gated_rms_norm(o, z, w, 1e-6, impl=impl)
        return jnp.sum(y.astype(F32) * dy), y
    (_, y), g = jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True))(o, z, w)
    return (y,) + g


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,heads,dim,extra", [
    ((2, 1056), 4, 128, 256),     # three blocks of 704 rows; the gate behind 256 other channels
    ((2, 48), 2, 128, 0),         # the gate an array of its own
    ((64,), 3, 128, 128),         # an odd number of heads: blocks of one head
    ((2, 40, 2), 1, 256, 512),    # a head of two lane blocks
])
def test_gated_norm_kernels_match_the_xla_composition(dtype, rows, heads, dim, extra):
    """Value, ``do``, ``dz`` (zeros in the channels that do not gate) and the
    weight's gradient; the statistics are recomputed in the backward."""
    o, z, w, dy = norm_case(dtype, rows, heads, dim, extra)
    assert norm_shapes_ok(o, z)
    got = norm_value_and_grads("pallas", o, z, w, dy)
    want = norm_value_and_grads("xla", o, z, w, dy)
    for a, b, what in zip(got, want, ("y", "do", "dz", "dw")):
        assert a.shape == b.shape and a.dtype == b.dtype
        close(a, b, dtype, what)
    assert not np.any(np.asarray(got[2][..., :extra], np.float32))


def test_gated_norm_takes_a_gate_in_the_inputs_shape():
    o, z, w, dy = norm_case(jnp.float32, (2, 32), 2, 128, 0)
    for impl in ("pallas", "xla"):
        a = norm_value_and_grads(impl, o, z.reshape(o.shape), w, dy)
        b = norm_value_and_grads(impl, o, z, w, dy)
        for m, n in zip(a, b):
            np.testing.assert_array_equal(m.reshape(n.shape), n)


@pytest.mark.parametrize("rows,heads,dim,extra", [
    ((2, 33), 2, 128, 0),      # rows outside whole sublane tiles
    ((2, 32), 3, 16, 0),       # a head outside whole lanes
    ((2, 32), 2, 128, 64),     # the gate starts inside a lane block
])
def test_shapes_the_norm_kernels_refuse_take_the_xla_path(rows, heads, dim, extra):
    o, z, w, dy = norm_case(jnp.float32, rows, heads, dim, extra)
    assert not norm_shapes_ok(o, z)
    for a, b in zip(norm_value_and_grads("auto", o, z, w, dy),
                    norm_value_and_grads("xla", o, z, w, dy)):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="tiling"):
        gated_rms_norm(o, z, w, impl="pallas")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_checkpoint_around_the_mixer_gives_the_unwrapped_mixers_gradients(dtype):
    """The kernels' residuals are their inputs: recomputing the mixer in the
    backward pass changes nothing."""
    c = HybridDecoderConfig(vocab_size=64, hidden_size=128, layer_types=("linear",),
                            linear_key_heads=1, linear_value_heads=1, delta_impl="pallas",
                            dtype=dtype)
    model = HybridDecoderModel(c)
    p = jax.tree.map(lambda a: a[0], model.init(jax.random.PRNGKey(0))["layers"]["gdn"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 128)).astype(dtype)
    r = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    loss = lambda f: lambda p, x: jnp.sum(f(p, x).astype(F32) * r)  # noqa: E731
    plain = jax.jit(jax.grad(loss(model._delta_mixer), argnums=(0, 1)))(p, x)
    again = jax.jit(jax.grad(loss(jax.checkpoint(model._delta_mixer)), argnums=(0, 1)))(p, x)
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    # and the kernels were in it
    text = str(jax.make_jaxpr(jax.grad(loss(model._delta_mixer)))(p, x))
    for name in ("conv_silu_fwd", "conv_silu_bwd", "gated_norm_fwd", "gated_norm_bwd"):
        assert name in text


def test_mixer_on_the_kernels_matches_the_mixer_on_xla():
    """Forward and every gradient of one delta-rule mixer, float32, the three
    stages on their kernels against the three XLA forms."""
    def grads(impl):
        c = HybridDecoderConfig(vocab_size=64, hidden_size=128, layer_types=("linear",),
                                linear_key_heads=1, linear_value_heads=2, delta_impl=impl)
        model = HybridDecoderModel(c)
        p = jax.tree.map(lambda a: a[0], model.init(jax.random.PRNGKey(0))["layers"]["gdn"])
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 128))
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                lambda p, x: jnp.sum(model._delta_mixer(p, x) ** 2), argnums=(0, 1)))(p, x)
    (a, ga), (b, gb) = grads("pallas"), grads("xla")
    np.testing.assert_allclose(a, b, rtol=1e-5)
    for m, n in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(m, n, atol=2e-5 * float(jnp.max(jnp.abs(n))) + 1e-9)
