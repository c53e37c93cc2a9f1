"""The ``afmoe`` build of the hybrid decoder (windowed and full gated
attention, sandwich norms, a dense layer, sigmoid-routed experts under a
selection bias) against its plain reference on seeded weights: the windowed
and the full mixer alone, the whole model's loss and every gradient tensor,
and three training steps with the bias's own update between them."""
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
from apex_tpu.optimizers import fused_adam  # noqa: E402
from apex_tpu.transformer.moe import router_bias_update  # noqa: E402
from benchmarks.adapters import afmoe_tree  # noqa: E402
from benchmarks.reference import afmoe_ref as R  # noqa: E402
from comparisons import batch, close  # noqa: E402

PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the published order cut as the cell cuts it: layer 0 (dense) and one period;
# 16 experts top-4, a share of 8 held; a window shorter than the rows
TOY = dict(hidden_size=128, num_hidden_layers=5, num_dense_layers=1, num_attention_heads=4,
           num_key_value_heads=2, head_dim=128, sliding_window=24, rope_theta=10000,
           intermediate_size=256, moe_intermediate_size=128, num_experts=8,
           num_experts_per_tok=4, num_shared_experts=1, route_norm=True, route_scale=2.826,
           score_func="sigmoid", load_balance_coeff=0.001, mup_enabled=True,
           rms_norm_eps=1e-5, vocab_size=256, layer_types=PERIOD * 2,
           layers_kept=[0, 4, 5, 6, 7], router_num_experts=16, experts_held_first=4)


def build(**settings):
    d = R.dims(TOY)
    model = HybridDecoderModel(HybridDecoderConfig(**afmoe_tree.config_kwargs(d, **settings)))
    return d, model, R.make_weights(d, R.seed_key(3))


def test_dims_cut_the_published_order_as_the_cell_does():
    d = R.dims(TOY)
    assert d["layer_types"] == ("window", "window", "window", "window", "full")
    assert d["ffn_types"] == ("dense", "moe", "moe", "moe", "moe")
    assert d["experts_held"] == (4, 8) and d["embed_scale"] == 128 ** 0.5
    with pytest.raises(ValueError, match="layers_kept"):
        R.dims(dict(TOY, layers_kept=[0, 1]))


@pytest.mark.parametrize("kind", ["window", "full"])
def test_attention_mixers_match_the_reference(kind):
    """Plain q/k norm, rotary on the whole head (windowed) or none (full),
    the band, grouped kv heads, the separate gate."""
    d, model, w = build(attention_impl="xla")
    i = d["layer_types"].index(kind)
    lw = jax.tree.map(lambda a: a[i], w["attn"])
    p = jax.tree.map(lambda a: a[i], afmoe_tree.to_program(w)["layers"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 80, 128))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(lambda s: R.attention_mixer(lw, d, s, kind, "float32", 16)))(x)
        mixer = jax.jit(model._attention_mixer, static_argnums=2)
        close(mixer(p, x, kind), want, 2e-5)
        other = "full" if kind == "window" else "window"
        assert float(jnp.max(jnp.abs(mixer(p, x, other) - want))) > 1e-3


@pytest.fixture(scope="module")
def reference():
    d, model, w = build()
    tokens, targets = batch(2, 128)
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(1), model.init_router_bias().shape)
    with jax.default_matmul_precision("highest"):
        (want, counts), gr = jax.jit(jax.value_and_grad(
            lambda w: R.loss(w, bias, d, tokens, targets), has_aux=True))(w)
    return bias, float(want), np.asarray(counts), afmoe_tree.to_program(gr)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_loss_and_every_gradient_match_the_reference(impl, reference):
    """Rows of 128 against a window of 24: the banded kernels in interpret
    mode on three layers, the unbanded on the fourth; the bias off zero."""
    bias, want, counts, want_g = reference
    d, model, w = build(attention_impl=impl, experts_impl=impl)
    p = afmoe_tree.to_program(w)
    assert jax.tree.structure(p) == jax.tree.structure(model.init(jax.random.PRNGKey(0)))
    assert bias.shape == model.init_router_bias().shape
    tokens, targets = batch(2, 128)
    with jax.default_matmul_precision("highest"):
        (loss, aux), g = jax.jit(jax.value_and_grad(
            lambda p: model.loss_fn(p, tokens, targets, return_aux=True, router_bias=bias),
            has_aux=True))(p)
        unbiased = jax.jit(model.loss_fn)(p, tokens, targets)
    assert abs(float(loss) - want) < 2e-5 and abs(float(unbiased) - want) > 1e-5
    np.testing.assert_array_equal(aux["router_counts"], counts)
    np.testing.assert_array_equal(aux["expert_load"], counts[:, 4:12])
    assert int(aux["dropped"]) == 0 and float(aux["load_balance_loss"]) == 0.0
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g)[0], jax.tree.leaves(want_g)):
        close(a, b, 2e-4 if impl == "pallas" else 2e-5, jax.tree_util.keystr(path))


def test_three_steps_with_the_bias_update_follow_the_reference():
    """Adam on the parameters, the bias moved by the step's counts and by no
    gradient: losses, weights and bias after three steps. (An ``eps`` that
    the gradients do not vanish against: at 1e-8 Adam's first step is
    ``lr * sign(g)``, and float32 noise on a gradient near 0 decides it.)"""
    d, model, w = build(attention_impl="xla", experts_impl="xla")
    p = afmoe_tree.to_program(w)
    opt = fused_adam(1e-2, eps=1e-2)
    state, bias = opt.init(p), model.init_router_bias()
    ref_opt, ref_bias = R.adam_init(w), R.bias_init(d)
    assert bias.shape == ref_bias.shape == (4, 16)

    @jax.jit
    def step(p, state, bias, tokens, targets):
        (loss, aux), g = jax.value_and_grad(
            lambda p: model.loss_fn(p, tokens, targets, return_aux=True, router_bias=bias),
            has_aux=True)(p)
        updates, state = opt.update(g, state, p)
        p = jax.tree.map(lambda a, u: a + u, p, updates)
        return p, state, router_bias_update(bias, aux["router_counts"], 0.001), loss

    ref_step = jax.jit(lambda w, o, b, tokens, targets: R.train_step(
        w, o, b, d, tokens, targets, lr=1e-2, eps=1e-2))
    with jax.default_matmul_precision("highest"):
        for i in range(3):
            tokens, targets = batch(2, 64, seed=i)
            p, state, bias, loss = step(p, state, bias, tokens, targets)
            w, ref_opt, ref_bias, want, _, counts = ref_step(w, ref_opt, ref_bias, tokens, targets)
            assert abs(float(loss) - float(want)) < 5e-5, i
    np.testing.assert_allclose(bias, ref_bias, atol=1e-7)
    # every move is one rate up, down or none; a layer's moves are not all alike
    steps = np.asarray(bias) / 0.001
    np.testing.assert_allclose(steps, np.round(steps), atol=1e-3)
    assert np.abs(steps).max() <= 3 and (np.ptp(steps, axis=-1) > 0).all()
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(p)[0],
                            jax.tree.leaves(afmoe_tree.to_program(w))):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=jax.tree_util.keystr(path))


def test_remat_and_spans_leave_the_loss_alone():
    d, model, w = build(attention_impl="xla", experts_impl="xla")
    _, again, _ = build(attention_impl="xla", experts_impl="xla", remat=True)
    p = afmoe_tree.to_program(w)
    tokens, targets = batch(1, 64)
    grad = jax.jit(jax.grad(model.loss_fn))
    g = grad(p, tokens, targets)
    gr = jax.jit(jax.grad(again.loss_fn))(p, tokens, targets)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gr)):
        close(a, b, 1e-5)
    text = grad.lower(p, tokens, targets).as_text(debug_info=True)
    for scope in ("hybrid/attn_win", "hybrid/attn", "hybrid/dense", "hybrid/moe", "moe/route",
                  "moe/experts", "moe/shared"):
        assert scope in text, scope
