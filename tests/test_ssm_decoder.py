"""The ``nemotron_h`` decoder (Mamba-2 state-space mixers, blocks of one half,
ungated relu2 experts, attention without gate, norms or rotary) against its
plain reference ``benchmarks/reference/ssm_ref.py`` on seeded weights, at a
small size with the published proportions: the mixer alone, the loss and every
gradient leaf (XLA and the kernels in interpret mode), the sixteen shares of
an expert layer that add up to the uncut layer, the block without a second
half, the spans, and what ``remat`` keeps of a state-space half."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu.models import HybridDecoderConfig, HybridDecoderModel, hybrid_decoder  # noqa: E402
from apex_tpu.ops.ssd import SSD_SAVED  # noqa: E402
from apex_tpu.transformer import moe  # noqa: E402
from benchmarks.adapters import ssm_tree  # noqa: E402
from benchmarks.reference import ssm_ref  # noqa: E402
from comparisons import gap, kernel_calls  # noqa: E402

# the cell's cut at a small size: published layers 0-6 (MEMEM*E), heads of 64
# two a lane tile, a state of 128 rows, 2 groups, chunk 128; 16 experts top-4
# with a share of 4 held; widths the kernels take
SMALL = {
    "hidden_size": 128, "num_hidden_layers": 7, "hybrid_override_pattern": "MEMEM*EMEMEM*E",
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "mamba_num_heads": 4, "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 2,
    "conv_kernel": 4, "chunk_size": 128, "use_conv_bias": True, "mlp_hidden_act": "relu2",
    "moe_intermediate_size": 192, "moe_shared_expert_intermediate_size": 128,
    "n_routed_experts": 4, "num_experts_per_tok": 4, "n_shared_experts": 1,
    "norm_topk_prob": True, "n_group": 1, "routed_scaling_factor": 2.5,
    "layer_norm_epsilon": 1e-5, "vocab_size": 256, "router_num_experts": 16,
    "experts_held_first": 4, "bias_update_rate": 0.001,
}
ROWS, SEQ = 2, 256


def build(**settings):
    d = ssm_ref.dims(SMALL)
    model = HybridDecoderModel(HybridDecoderConfig(**ssm_tree.config_kwargs(d, **settings)))
    return d, model, ssm_ref.make_weights(d, ssm_ref.seed_key(3))


def batch():
    tokens = jax.random.randint(jax.random.PRNGKey(1), (ROWS, SEQ), 0, SMALL["vocab_size"])
    return tokens, jnp.roll(tokens, -1, axis=1)


def test_dims_and_blocks_cut_the_published_model_as_the_cell_does():
    d = ssm_ref.dims(SMALL)
    assert d["kinds"] == ("ssm", "moe", "ssm", "moe", "ssm", "attn", "moe")
    assert (d["d_inner"], d["conv_dim"], d["experts_held"]) == (256, 768, (4, 4))
    laid = ssm_tree.blocks(d["kinds"])
    assert [b[:2] for b in laid] == [("ssm", "moe"), ("ssm", "moe"), ("ssm", "none"),
                                     ("full", "moe")]
    assert [b[2:] for b in laid] == [(0, 1), (2, 3), (4, None), (5, 6)]
    with pytest.raises(ValueError, match="no mixer before it"):
        ssm_tree.blocks(("moe", "ssm"))
    with pytest.raises(ValueError, match="no mixer before it"):
        ssm_tree.blocks(("ssm", "moe", "moe"))
    _, model, w = build()
    p = ssm_tree.to_program(w, d)
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))  # noqa: E731
    assert count(p) == count(w)                          # a relabelling: nothing lost or doubled
    init = jax.jit(model.init)(jax.random.PRNGKey(0))
    assert jax.tree.map(jnp.shape, p) == jax.tree.map(jnp.shape, init)
    assert p["layers"]["norm1"].shape == (4, 128) and p["layers"]["norm2"].shape == (3, 128)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_state_space_mixer_matches_the_reference(impl):
    d, model, w = build(delta_impl=impl)
    p = jax.tree.map(lambda a: a[1], ssm_tree.to_program(w, d)["layers"]["ssm"])
    lw = jax.tree.map(lambda a: a[1], w["ssm"])
    x = jax.random.normal(jax.random.PRNGKey(5), (ROWS, SEQ + 40, 128))     # a ragged last chunk
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda lw, x: ssm_ref.ssm_mixer(lw, d, x, "float32"))(lw, x)
        assert gap(jax.jit(model._ssm_mixer)(p, x), want) <= 2e-5


@pytest.fixture(scope="module")
def reference():
    d, _, w = build()
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(2), (3, 16))
    tokens, targets = batch()
    with jax.default_matmul_precision("highest"):
        (loss, counts), g = jax.jit(jax.value_and_grad(
            lambda w: ssm_ref.loss(w, bias, d, tokens, targets), has_aux=True))(w)
    return bias, float(loss), np.asarray(counts), ssm_tree.to_program(g, d)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_loss_and_every_gradient_match_the_reference(impl, reference):
    bias, want, counts, g_want = reference
    d, model, w = build(delta_impl=impl, experts_impl=impl, attention_impl="xla",
                        remat=impl == "pallas")
    tokens, targets = batch()
    with jax.default_matmul_precision("highest"):
        (loss, aux), g = jax.jit(jax.value_and_grad(lambda p: model.loss_fn(
            p, tokens, targets, return_aux=True, router_bias=bias), has_aux=True))(
                ssm_tree.to_program(w, d))
    assert abs(float(loss) - want) <= 2e-6 * want
    np.testing.assert_array_equal(aux["router_counts"], counts)
    np.testing.assert_array_equal(aux["expert_load"], counts[:, 4:8])
    assert int(aux["dropped"]) == 0 and float(aux["load_balance_loss"]) == 0.0
    leaves = jax.tree_util.tree_flatten_with_path(g)[0]
    assert len(leaves) == len(jax.tree.leaves(g_want)) == 22
    for (path, a), b in zip(leaves, jax.tree.leaves(g_want)):
        assert float(jnp.max(jnp.abs(b))) > 0, jax.tree_util.keystr(path)
        assert gap(a, b) <= 2e-5, jax.tree_util.keystr(path)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_sixteen_shares_add_up_to_the_uncut_layer(impl):
    """One chip of a 16-way expert-parallel job computes one of these: the
    router at its full width and the same bias in every share, two experts
    held; what every share computes alike (the shared expert) counted once,
    the routed parts add up to the uncut reference's layer."""
    H, F, Fs, E, K = 128, 192, 128, 32, 6
    d = {"router_num_experts": E, "num_experts_per_tok": K, "route_norm": True,
         "route_scale": 2.5, "experts_held": (0, E)}
    k = iter(jax.random.split(jax.random.PRNGKey(11), 8))
    n = lambda *s: 0.05 * jax.random.normal(next(k), s)  # noqa: E731
    w = {"router": n(H, E), "w_up": n(E, H, F), "w_down": n(E, F, H), "shared_up": n(H, Fs),
         "shared_down": n(Fs, H)}
    x, bias = jax.random.normal(next(k), (192, H)), 0.05 * jax.random.normal(next(k), (E,))
    with jax.default_matmul_precision("highest"):
        shared = ssm_ref.shared_expert(w, x, "float32")
        uncut = ssm_ref.expert_layer(w, bias, d, x, "float32")[0] + shared
        parts, ref_parts, loads = [], [], []
        for first in range(0, E, 2):
            held = dict(w, w_up=w["w_up"][first:first + 2], w_down=w["w_down"][first:first + 2])
            y, aux = moe.dropless_moe_layer(
                held, x, top_k=K, experts_held=(first, 2), impl=impl, score="sigmoid",
                route_scale=2.5, router_bias=bias, shared_gate=False, activation="relu2")
            parts.append(y - shared)
            ref_parts.append(ssm_ref.expert_layer(held, bias, d, x, "float32",
                                                  held=(first, 2))[0])
            loads.append(np.asarray(aux["expert_load"]))
            assert int(aux["dropped"]) == 0
    assert len(parts) == 16
    scale = float(jnp.max(jnp.abs(uncut)))
    np.testing.assert_allclose(sum(parts) + shared, uncut, atol=1e-5 * scale)
    np.testing.assert_allclose(sum(ref_parts) + shared, uncut, atol=1e-5 * scale)
    assert np.concatenate(loads).sum() == K * x.shape[0]


def test_a_block_without_a_second_half_is_its_mixer_alone():
    config = dict(vocab_size=64, hidden_size=128, num_heads=2, num_kv_heads=1, head_dim=64,
                  rotary_dim=0, attn_gate=False, qk_norm=False, ssm_heads=4, ssm_head_dim=64,
                  ssm_state=128, ssm_groups=2, router_experts=8, top_k=2, expert_ffn=64,
                  shared_ffn=64, router_score="sigmoid", shared_gate=False,
                  expert_activation="relu2", zero_centered_norm=False, delta_impl="xla",
                  attention_impl="xla", experts_impl="xla")
    model = HybridDecoderModel(HybridDecoderConfig(
        layer_types=("ssm", "full", "ssm"), ffn_types=("none", "moe", "none"), **config))
    p = jax.jit(model.init)(jax.random.PRNGKey(0))
    layers = p["layers"]
    assert layers["norm1"].shape == (3, 128) and layers["norm2"].shape == (1, 128)
    assert layers["moe"]["router"].shape[0] == 1 and "dense" not in layers
    assert set(layers["attn"]) == {"w_q", "w_k", "w_v", "w_o"}           # no gate, no norms
    assert layers["attn"]["w_q"].shape == (1, 128, 128)
    assert set(layers["moe"]) == {"router", "w_up", "w_down", "shared_up", "shared_down"}
    assert set(layers["ssm"]) == {"w_in", "conv_w", "conv_b", "A_log", "dt_bias", "D", "norm_w",
                                  "w_o"}
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 128), 0, 64)
    x, aux = jax.jit(model.hidden_states_with_aux)(p, tokens)
    assert aux["expert_load"].shape == (1, 8)

    def by_hand(p):
        """The lone expert half reads norm2's ONE row, the blocks around it
        are their mixers alone."""
        layers = p["layers"]
        take = lambda group, j: jax.tree.map(lambda a: a[j], layers[group])  # noqa: E731
        h = p["embedding"]["weight"][tokens]
        h = model._mixer_half("ssm")(take("ssm", 0), layers["norm1"][0], None, h)
        h = model._mixer_half("full")(take("attn", 0), layers["norm1"][1], None, h)
        h, aux = model._expert_half(take("moe", 0), layers["norm2"][0], None, None, h)
        h = model._mixer_half("ssm")(take("ssm", 1), layers["norm1"][2], None, h)
        return model._norm(h, p["norm_f"]), aux["expert_load"]

    want, load = jax.jit(by_hand)(p)
    assert gap(x, want) <= 1e-6
    np.testing.assert_array_equal(aux["expert_load"][0], load)
    with pytest.raises(ValueError, match="'moe', 'dense' or 'none'"):
        HybridDecoderConfig(layer_types=("ssm",), ffn_types=("nothing",))
    with pytest.raises(ValueError, match="expert_activation"):
        HybridDecoderConfig(expert_activation="gelu")
    with pytest.raises(ValueError, match="multiple of their groups"):
        HybridDecoderConfig(layer_types=("ssm",), ssm_heads=6, ssm_groups=4)


def test_float32_leaves_and_spans():
    d, model, w = build(delta_impl="xla", experts_impl="xla", attention_impl="xla")
    assert set(model.float32_params) == {"A_log", "dt_bias", "D"}
    p = ssm_tree.to_program(w, d)
    tokens, targets = batch()
    text = jax.jit(jax.grad(model.loss_fn)).lower(p, tokens[:1, :128], targets[:1, :128]).as_text(
        debug_info=True)
    for scope in ("hybrid/ssm", "hybrid/attn", "mix/proj_in", "mix/place", "mix/proj_out",
                  "hybrid/moe", "moe/route", "moe/experts", "moe/shared", "hybrid/unembed_xent"):
        assert scope in text, scope
    assert "hybrid/gdn" not in text and "hybrid/dense" not in text
    from apex_tpu.prof import scopes
    assert "hybrid/ssm" in scopes.SPANS


def grad_calls(remat):
    d, model, w = build(delta_impl="pallas", experts_impl="xla",
                        attention_impl="xla", remat=remat)
    tokens, targets = batch()
    return kernel_calls(jax.make_jaxpr(jax.grad(model.loss_fn))(
        ssm_tree.to_program(w, d), tokens, targets).jaxpr)


def test_the_scan_runs_once_a_layer_and_pass_under_remat(monkeypatch):
    assert set(SSD_SAVED) <= set(hybrid_decoder.MIXER_SAVED)
    plain, kept = grad_calls(False), grad_calls(True)
    assert kept["ssd_fwd"] == plain["ssd_fwd"] == kept["ssd_bwd"] == plain["ssd_bwd"] == 3
    # the convolution (three pieces a layer) and the gated norm are computed again
    assert plain["conv_silu_fwd"] == 9 and kept["conv_silu_fwd"] == 18
    assert plain["gated_norm_fwd"] == 3 and kept["gated_norm_bwd"] == 3
    # the witness: with no name kept every block runs its scan again
    monkeypatch.setattr(hybrid_decoder, "MIXER_SAVED", ())
    assert grad_calls(True)["ssd_fwd"] == 6
