"""The hybrid decoder (gated delta-rule and gated attention mixers, dropless
experts) against the plain reference on seeded weights: the attention mixer
alone, then the whole model's loss and every gradient tensor; and the
float32 leaves a mixed-precision policy leaves alone."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu import amp  # noqa: E402
from apex_tpu.models import HybridDecoderConfig, HybridDecoderModel  # noqa: E402
from benchmarks.adapters import hybrid_tree  # noqa: E402
from benchmarks.reference import hybrid_ref as R  # noqa: E402
from comparisons import batch, close  # noqa: E402

# two periods of (linear, full); 16 experts top-4, a share of 8 held
TOY = dict(hidden_size=128, num_hidden_layers=4, full_attention_interval=2,
           num_attention_heads=4, num_key_value_heads=2, head_dim=64,
           partial_rotary_factor=0.25, rope_theta=1e7, linear_num_key_heads=1,
           linear_num_value_heads=2, linear_key_head_dim=128, linear_value_head_dim=128,
           linear_conv_kernel_dim=4, num_experts=8, num_experts_per_tok=4,
           moe_intermediate_size=128, shared_expert_intermediate_size=128,
           rms_norm_eps=1e-6, vocab_size=256, norm_topk_prob=True,
           router_num_experts=16, experts_held_first=4)


def build(**settings):
    d = R.dims(TOY)
    model = HybridDecoderModel(HybridDecoderConfig(**hybrid_tree.config_kwargs(d, **settings)))
    return d, model, R.make_weights(d, R.seed_key(3))


def test_gated_attention_mixer_matches_the_reference():
    """q/k norm with a zero-centred weight, rotary on the first quarter of
    each head, grouped kv heads, the sigmoid gate."""
    d, model, w = build(attention_impl="xla")
    lw = jax.tree.map(lambda a: a[0], w["attn"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 80, 128))
    r = jax.random.normal(jax.random.PRNGKey(6), (2, 80, 128))
    with jax.default_matmul_precision("highest"):
        ref = lambda lw, x: jax.vmap(lambda s: R.gated_attention_mixer(lw, d, s, "float32", 16))(x)  # noqa: E731
        got, want = jax.jit(model._attention_mixer)(lw, x), jax.jit(ref)(lw, x)
        np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.max(jnp.abs(want))))
        g = jax.jit(jax.grad(lambda lw, x: jnp.sum(model._attention_mixer(lw, x) * r),
                             argnums=(0, 1)))(lw, x)
        gr = jax.jit(jax.grad(lambda lw, x: jnp.sum(ref(lw, x) * r), argnums=(0, 1)))(lw, x)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gr)):
        np.testing.assert_allclose(a, b, atol=3e-5 * float(jnp.max(jnp.abs(b))))


@pytest.fixture(scope="module")
def reference():
    d, _, w = build()
    tokens, targets = batch()
    with jax.default_matmul_precision("highest"):
        (want, loads), g_ref = jax.jit(jax.value_and_grad(
            lambda w: R.loss(w, d, tokens, targets, row_block=2), has_aux=True))(w)
    return float(want), np.asarray(loads), hybrid_tree.to_program(g_ref)


@pytest.mark.parametrize("impl,remat", [("xla", False), ("xla", True), ("pallas", True)])
def test_loss_and_every_gradient_match_the_reference(impl, remat, reference):
    want, loads, want_g = reference
    d, model, w = build(attention_impl="xla", delta_impl=impl, experts_impl=impl, remat=remat)
    tokens, targets = batch()
    with jax.default_matmul_precision("highest"):
        (got, aux), g = jax.jit(jax.value_and_grad(
            lambda p: model.loss_fn(p, tokens, targets, return_aux=True), has_aux=True))(
                hybrid_tree.to_program(w))
    assert abs(float(got) - want) < 2e-6 * abs(want)
    np.testing.assert_array_equal(aux["expert_load"], loads)
    assert int(aux["dropped"]) == 0 and aux["expert_load"].shape == (4, 8)
    paths = jax.tree_util.tree_flatten_with_path(g)[0]
    assert len(paths) == 24
    for (path, a), b in zip(paths, jax.tree.leaves(want_g)):
        close(a, b, 2e-4, jax.tree_util.keystr(path))


def test_loss_fn_has_the_trainers_signature_and_init_the_programs_tree():
    d, model, w = build(attention_impl="xla", delta_impl="xla", experts_impl="xla")
    p = jax.jit(model.init)(jax.random.PRNGKey(0))
    want = hybrid_tree.to_program(w)
    assert jax.tree.structure(p) == jax.tree.structure(want)
    assert jax.tree.map(jnp.shape, p) == jax.tree.map(jnp.shape, want)
    tokens, targets = batch(1, 64)
    loss = jax.jit(model.loss_fn)(p, tokens, targets)
    masked = jax.jit(lambda p, mask: model.loss_fn(p, tokens, targets, loss_mask=mask))(
        p, jnp.ones_like(tokens).at[:, 32:].set(0))
    assert loss.shape == () and np.isfinite(float(loss)) and float(masked) != float(loss)
    assert jax.jit(model.logits)(p, tokens).shape == (1, 64, 256)
    with pytest.raises(ValueError, match="layer_types"):
        HybridDecoderConfig(layer_types=("linear", "sparse"))
    with pytest.raises(ValueError, match="window="):
        HybridDecoderConfig(layer_types=("linear", "window"))
    with pytest.raises(ValueError, match="ffn_types"):
        HybridDecoderConfig(ffn_types=("dense",))


def test_o2_keeps_the_named_leaves_in_float32():
    """A log decay enters an exponent: its bf16 rounding is a 1 % change of
    the decay. ``keep_float32`` keeps such leaves out of the model copy's
    cast; without it the policy casts every leaf, as before."""
    _, model, w = build()
    p = hybrid_tree.to_program(w)
    policy = amp.get_policy("O2")
    kept = amp.MasterWeights.create(p, policy, keep_float32=model.float32_params)
    gdn = kept.model["layers"]["gdn"]
    assert gdn["A_log"].dtype == gdn["dt_bias"].dtype == jnp.float32
    assert gdn["w_qkvz"].dtype == kept.model["norm_f"].dtype == jnp.bfloat16
    after = amp.apply_updates_with_master(kept, jax.tree.map(jnp.zeros_like, kept.master))
    assert after.model["layers"]["gdn"]["A_log"].dtype == jnp.float32
    plain = amp.MasterWeights.create(p, policy)
    assert {a.dtype for a in jax.tree.leaves(plain.model)} == {jnp.dtype(jnp.bfloat16)}
