"""The dropless expert layer against the plain reference: uneven routing,
nothing dropped, the block-after-block path, and the share test — the
partial results of all shares, the shared expert counted once, add up to the
uncut layer's result; the same under the sigmoid router and its selection
bias, and with ungated relu2 experts."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu.transformer import moe  # noqa: E402
from benchmarks.reference import afmoe_ref as A  # noqa: E402
from benchmarks.reference import hybrid_ref as R  # noqa: E402
from moe_toy import E, F, H, K, SIG, program, reference, weights  # noqa: E402


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("tokens", [(2, 96), (3, 100)])   # 192 rows a block, or 300 in 384
def test_uneven_routing_matches_the_reference_and_drops_nothing(impl, tokens, monkeypatch):
    """All 16 experts held and blocks of as many rows as tokens, so the
    routing fills top_k blocks and part of one more: the loop over blocks
    runs, forward and backward."""
    monkeypatch.setattr(moe, "dropless_block_rows", lambda tokens, *_: tokens)
    x = jax.random.normal(jax.random.PRNGKey(7), tokens + (H,))
    # positive features, so that a column of the router decides: expert 5
    # gets nearly every token, expert 3 none
    x = jnp.abs(x)
    skew = jnp.zeros((H, E)).at[:, 5].set(0.3).at[:, 3].set(-0.3)
    w = weights(skew=skew)
    flat = x.reshape(-1, H)
    with jax.default_matmul_precision("highest"):
        f = lambda p, x: moe.dropless_moe_layer(  # noqa: E731
            p, x, top_k=K, experts_held=(0, E), impl=impl)
        y, aux = jax.jit(f)(program(w), x)
        want, want_aux, want_load = jax.jit(reference)(w, flat)
        load = np.asarray(aux["expert_load"])
        assert load[3] == 0 and load[5] >= 0.9 * flat.shape[0] and load.sum() == K * flat.shape[0]
        assert int(aux["dropped"]) == 0
        np.testing.assert_array_equal(load, np.asarray(want_load))
        np.testing.assert_allclose(y.reshape(-1, H), want, atol=2e-5 * float(jnp.max(jnp.abs(want))))
        np.testing.assert_allclose(aux["load_balance_loss"], want_aux, rtol=1e-5)
        r = jax.random.normal(jax.random.PRNGKey(8), want.shape)
        loss = lambda p, x: jnp.sum(f(p, x)[0].reshape(-1, H) * r) + f(p, x)[1]["load_balance_loss"]  # noqa: E731
        ref_loss = lambda w, x: (lambda o: jnp.sum(o[0] * r) + o[1])(reference(w, x))  # noqa: E731
        gp, gx = jax.jit(jax.grad(loss, argnums=(0, 1)))(program(w), x)
        gw, gxr = jax.jit(jax.grad(ref_loss, argnums=(0, 1)))(w, flat)
    want_p = program(gw)
    for name in gp:
        np.testing.assert_allclose(gp[name], want_p[name], err_msg=name,
                                   atol=3e-5 * float(jnp.max(jnp.abs(want_p[name]))) + 1e-8)
    np.testing.assert_allclose(gx.reshape(-1, H), gxr, atol=3e-5 * float(jnp.max(jnp.abs(gxr))))


def test_shares_add_up_to_the_uncut_layer():
    """Four shares of four experts each: what every share computes alike
    (the shared expert) counted once, the parts add up to the whole layer —
    in the program and in the reference alike."""
    x = jax.random.normal(jax.random.PRNGKey(9), (192, H))
    w = weights(seed=1)
    with jax.default_matmul_precision("highest"):
        whole, _ = moe.dropless_moe_layer(program(w), x, top_k=K, impl="xla")
        shared = R.shared_expert(w, x, "float32")
        parts, ref_parts, loads = [], [], []
        for first in range(0, E, 4):
            y, aux = moe.dropless_moe_layer(program(w, first, 4), x, top_k=K,
                                            experts_held=(first, 4), impl="xla")
            parts.append(y - shared)
            ref_parts.append(reference(w, x, first, 4)[0] - shared)
            loads.append(np.asarray(aux["expert_load"]))
            assert int(aux["dropped"]) == 0
        uncut = reference(w, x)[0]
    scale = float(jnp.max(jnp.abs(uncut)))
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-5 * scale)
    np.testing.assert_allclose(sum(ref_parts) + shared, uncut, atol=1e-5 * scale)
    np.testing.assert_allclose(whole, uncut, atol=2e-5 * scale)
    assert np.concatenate(loads).sum() == K * x.shape[0]


def test_block_rows_keep_clear_of_the_expected_load(monkeypatch):
    """A small share of the experts: a block of as many rows as tokens where
    the expected load with its tile padding fits one (32 of 512 at top-10:
    10,240 + 4,096 of 16,384), two where it sits at the block's end (16 of
    128 at top-8: 16,384 + 2,048), all of them where every expert is held;
    the blocks' size changes no result."""
    assert moe.dropless_block_rows(16384, 10, 32, 512) == 16384
    assert moe.dropless_block_rows(16384, 8, 16, 128) == 2 * 16384
    assert moe.dropless_block_rows(16384, 8, 8, 128) == 16384
    assert moe.dropless_block_rows(192, K, E, E) == (K + 11) * 192    # 768 + 2,048 rows of padding
    x = jax.random.normal(jax.random.PRNGKey(21), (192, H))
    w = weights(seed=4)
    with jax.default_matmul_precision("highest"):
        whole, aux = moe.dropless_moe_layer(program(w), x, top_k=K, impl="xla")
        for rows in (128, 192, 1024):
            monkeypatch.setattr(moe, "dropless_block_rows", lambda *_, rows=rows: rows)
            y, a = moe.dropless_moe_layer(program(w), x, top_k=K, impl="xla")
            np.testing.assert_allclose(y, whole, atol=1e-5 * float(jnp.max(jnp.abs(whole))))
            np.testing.assert_array_equal(a["expert_load"], aux["expert_load"])
            assert int(a["dropped"]) == 0


def test_experts_held_must_match_the_matrices_given():
    w = weights()
    with pytest.raises(ValueError, match="experts_held"):
        moe.dropless_moe_layer(program(w, 0, 4), jnp.zeros((8, H)), top_k=K, experts_held=(0, 8))
    with pytest.raises(ValueError, match="experts_held"):
        moe.dropless_moe_layer(program(w, 0, 4), jnp.zeros((8, H)), top_k=K, experts_held=(14, 4))


def test_no_local_assignment_at_all_gives_the_shared_expert_alone():
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(11), (64, H)))
    w = weights(skew=jnp.zeros((H, E)).at[:, :4].set(-1.0))   # nobody picks experts 0-3
    for impl in ("xla", "pallas"):
        def total(p):
            y, aux = moe.dropless_moe_layer(p, x, top_k=K, experts_held=(0, 4), impl=impl)
            return jnp.sum(y), (y, aux)
        (_, (y, aux)), g = jax.jit(jax.value_and_grad(total, has_aux=True))(program(w, 0, 4))
        assert int(aux["expert_load"].sum()) == 0
        np.testing.assert_allclose(y, R.shared_expert(w, x, "float32"), atol=1e-5)
        assert float(jnp.max(jnp.abs(g["w_gate_up"]))) == 0.0
        assert float(jnp.max(jnp.abs(g["w_down"]))) == 0.0


def sigmoid_program(w, first=0, count=E):
    p = program(w, first, count)
    del p["shared_mix"]                                    # the shared expert ungated
    return p


def sigmoid_layer(w, x, bias, first=0, count=E, impl="xla"):
    return moe.dropless_moe_layer(sigmoid_program(w, first, count), x, top_k=K,
                                  experts_held=(first, count), impl=impl, score="sigmoid",
                                  route_scale=2.826, router_bias=bias, shared_gate=False)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_sigmoid_layer_matches_the_reference(impl):
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 96, H))
    w = weights(seed=2)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(13), (E,))
    flat = x.reshape(-1, H)
    ref = lambda w, x: (A.expert_layer(w, bias, SIG, x, "float32")[0]  # noqa: E731
                        + A.shared_expert(w, x, "float32"))
    with jax.default_matmul_precision("highest"):
        y, aux = sigmoid_layer(w, x, bias, impl=impl)
        want = ref(w, flat)
        np.testing.assert_allclose(y.reshape(-1, H), want, atol=2e-5 * float(jnp.max(jnp.abs(want))))
        np.testing.assert_array_equal(aux["router_counts"],
                                      A.route(flat, w["router"], bias, SIG, "float32")[2])
        assert int(aux["dropped"]) == 0 and int(aux["expert_load"].sum()) == K * flat.shape[0]
        r = jax.random.normal(jax.random.PRNGKey(14), want.shape)
        gp = jax.jit(jax.grad(lambda p: jnp.sum(moe.dropless_moe_layer(
            p, x, top_k=K, impl=impl, score="sigmoid", route_scale=2.826,
            router_bias=bias, shared_gate=False)[0].reshape(-1, H) * r)))(sigmoid_program(w))
        gw = sigmoid_program(jax.jit(jax.grad(lambda w: jnp.sum(ref(w, flat) * r)))(w))
    for name in gp:
        np.testing.assert_allclose(gp[name], gw[name], err_msg=name,
                                   atol=3e-5 * float(jnp.max(jnp.abs(gw[name]))) + 1e-8)


def test_eight_sigmoid_shares_add_up_to_the_uncut_layer():
    """The cell's cut at a small size: eight shares of two experts each, the
    router at its full width in every one and the same bias; what every share
    computes alike (the shared expert) counted once, the routed parts add up
    to the uncut reference's layer."""
    x = jax.random.normal(jax.random.PRNGKey(15), (192, H))
    w = weights(seed=3)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(16), (E,))
    with jax.default_matmul_precision("highest"):
        shared = A.shared_expert(w, x, "float32")
        uncut = A.expert_layer(w, bias, SIG, x, "float32")[0] + shared
        parts, ref_parts, loads = [], [], []
        for first in range(0, E, 2):
            y, aux = sigmoid_layer(w, x, bias, first, 2)
            parts.append(y - shared)
            lw = dict(w, **{n: w[n][first:first + 2] for n in ("w_gate", "w_up", "w_down")})
            ref_parts.append(A.expert_layer(lw, bias, SIG, x, "float32", held=(first, 2))[0])
            loads.append(np.asarray(aux["expert_load"]))
            assert int(aux["dropped"]) == 0
    assert len(parts) == 8
    scale = float(jnp.max(jnp.abs(uncut)))
    np.testing.assert_allclose(sum(parts) + shared, uncut, atol=1e-5 * scale)
    np.testing.assert_allclose(sum(ref_parts) + shared, uncut, atol=1e-5 * scale)
    assert np.concatenate(loads).sum() == K * x.shape[0]


RELU2 = {"router_num_experts": E, "num_experts_per_tok": K, "route_norm": True,
         "route_scale": 2.5, "experts_held": (0, E)}


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("F2", [128, 192])       # whole lane tiles, and one and a half
def test_relu2_experts_match_the_reference_and_every_gradient(impl, F2):
    """Ungated experts with ONE up matrix, ``relu(x W_up)^2 W_down``, the
    shared expert the same form: value, loads and the gradient of every leaf
    (the up matrices' every column among them) against ``ssm_ref``."""
    from benchmarks.reference import ssm_ref as S
    k = iter(jax.random.split(jax.random.PRNGKey(21), 8))
    n = lambda *s: 0.05 * jax.random.normal(next(k), s)  # noqa: E731
    w = {"router": n(H, E), "w_up": n(E, H, F2), "w_down": n(E, F2, H), "shared_up": n(H, F),
         "shared_down": n(F, H)}
    x, bias = jax.random.normal(next(k), (2, 96, H)), 0.05 * jax.random.normal(next(k), (E,))
    ct = jax.random.normal(next(k), (192, H))
    held = (4, 8)
    cut = lambda w: dict(w, w_up=w["w_up"][4:12], w_down=w["w_down"][4:12])  # noqa: E731

    def layer(w, x):
        y, aux = moe.dropless_moe_layer(
            cut(w), x, top_k=K, experts_held=held, impl=impl, score="sigmoid", route_scale=2.5,
            router_bias=bias, shared_gate=False, activation="relu2")
        return jnp.sum(y.reshape(-1, H) * ct), aux

    def ref(w, x):
        m = x.reshape(-1, H)
        y, counts = S.expert_layer(cut(w), bias, RELU2, m, "float32", held=held)
        return jnp.sum((y + S.shared_expert(w, m, "float32")) * ct), counts

    with jax.default_matmul_precision("highest"):
        (got, aux), g = jax.jit(jax.value_and_grad(layer, argnums=(0, 1), has_aux=True))(w, x)
        (want, counts), g_want = jax.jit(jax.value_and_grad(
            ref, argnums=(0, 1), has_aux=True))(w, x)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_array_equal(aux["router_counts"], counts)
    assert int(aux["dropped"]) == 0
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g)[0], jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.max(jnp.abs(b))),
                                   err_msg=jax.tree_util.keystr(path))
    dw_up = g[0]["w_up"][4:12]
    assert float(jnp.min(jnp.max(jnp.abs(dw_up), axis=(0, 1)))) > 0       # every column written
    with pytest.raises(ValueError, match="activation"):
        moe.dropless_moe_layer(cut(w), x, top_k=K, experts_held=held, activation="gelu")
    with pytest.raises(KeyError):            # a SwiGLU layer's leaves are not these
        moe.dropless_moe_layer(cut(w), x, top_k=K, experts_held=held)
