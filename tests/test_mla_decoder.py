"""The ``deepseek_v2`` build of the hybrid decoder (latent attention with one
shared rotary key under yarn, a dense layer, softmax-routed experts beside two
shared ones, the balance term per sequence) against its plain reference on
seeded weights: the yarn frequencies and the balance term by hand, the mixer
alone, the whole model's loss and every gradient tensor, and the cut itself —
the eight shares' routed parts with the shared experts counted once add up to
the uncut layer."""
import math
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
from apex_tpu.ops import rotary  # noqa: E402
from apex_tpu.transformer.moe import route_topk  # noqa: E402
from benchmarks.adapters import mla_tree  # noqa: E402
from benchmarks.reference import mla_ref as R  # noqa: E402
from comparisons import batch, close  # noqa: E402

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096, "type": "yarn"}
# the cell's cut at a toy size: the leading dense layer and two expert
# layers; 16 experts top-4 in 8 shares of 2; a yarn ramp that lies inside
# rows of 128 (pairs 0-6 of 32)
TOY = dict(hidden_size=128, num_hidden_layers=3, first_k_dense_replace=1,
           num_attention_heads=4, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
           kv_lora_rank=64, rope_theta=10000,
           rope_scaling=dict(YARN, factor=4, original_max_position_embeddings=32),
           intermediate_size=256, moe_intermediate_size=128, n_routed_experts=2,
           num_experts_per_tok=4, n_shared_experts=2, norm_topk_prob=False,
           routed_scaling_factor=1, scoring_func="softmax", seq_aux=True,
           aux_loss_alpha=0.001, rms_norm_eps=1e-6, vocab_size=256, q_lora_rank=None,
           topk_method="greedy", layers_kept=[0, 1, 2], router_num_experts=16,
           experts_held_first=6)


def build(config=TOY, **settings):
    d = R.dims(config)
    model = HybridDecoderModel(HybridDecoderConfig(**mla_tree.config_kwargs(d, **settings)))
    return d, model, R.make_weights(d, R.seed_key(3))


def test_dims_cut_the_published_model_as_the_cell_does():
    d = R.dims(TOY)
    assert d["layer_types"] == ("latent",) * 3 and d["ffn_types"] == ("dense", "moe", "moe")
    assert d["experts_held"] == (6, 2) and d["shared_intermediate_size"] == 256
    with pytest.raises(ValueError, match="layers_kept"):
        R.dims(dict(TOY, layers_kept=[0, 1]))
    with pytest.raises(ValueError, match="low-rank query"):
        R.dims(dict(TOY, q_lora_rank=1536))
    with pytest.raises(ValueError, match="per sequence"):
        R.dims(dict(TOY, seq_aux=False))


def test_yarn_frequencies_by_hand():
    """The published entry over 32 pairs: pairs below 10 keep their
    frequency, pairs from 23 on take it over 40, a linear blend between; the
    softmax scale carries the temperature squared."""
    d = dict(R.dims(TOY), rope_scaling=YARN)
    low = 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000))
    high = 64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(10000))
    assert (math.floor(low), math.ceil(high)) == (10, 23)
    want = []
    for i in range(32):
        f = 10000 ** (-2 * i / 64)
        ramp = min(max((i - 10) / 13, 0.0), 1.0)
        want.append(f * (1 - ramp) + f / 40 * ramp)
    np.testing.assert_allclose(R.yarn_frequencies(d), want, rtol=1e-6)
    got, on_both = rotary.yarn_frequencies(64, 10000, YARN)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert on_both == 1.0 and want[9] == 10000 ** (-18 / 64)
    assert want[23] == pytest.approx(10000 ** (-46 / 64) / 40)
    assert R.score_scale(d) == pytest.approx(0.07217 * 1.5896, rel=1e-4)
    assert rotary.yarn_mscale(40, 0.707) == pytest.approx(0.1 * 0.707 * math.log(40) + 1)
    # a factor on cos and sin where the two temperatures differ
    _, other = rotary.yarn_frequencies(64, 10000, dict(YARN, mscale_all_dim=0.0))
    assert other == pytest.approx(rotary.yarn_mscale(40, 0.707))
    with pytest.raises(ValueError, match="yarn"):
        rotary.yarn_frequencies(64, 10000, {"type": "linear", "factor": 2})


def test_latent_mixer_matches_the_reference():
    """The low-rank key/value path with its norm, the rotary features under
    yarn, ONE rotary key for all heads, the temperature on the scores."""
    d, model, w = build(attention_impl="xla")
    lw = jax.tree.map(lambda a: a[1], w["attn"])
    p = jax.tree.map(lambda a: a[1], mla_tree.to_program(w)["layers"]["mla"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 96, 128))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(lambda s: R.attention_mixer(lw, d, s, "float32", 32)))(x)
        close(jax.jit(model._latent_mixer)(p, x), want, 2e-5)
        plain = HybridDecoderModel(HybridDecoderConfig(**dict(
            mla_tree.config_kwargs(d, attention_impl="xla"), rope_scaling=None)))
        assert float(jnp.max(jnp.abs(jax.jit(plain._latent_mixer)(p, x) - want))) > 1e-4


def test_balance_term_per_sequence_by_hand():
    """For each row f_i = E n_i / (k S) and P_i the row's mean probability;
    the term is the mean over the rows of sum_i f_i P_i — not the batch-wise
    term, which ``sequences=None`` still gives bit for bit."""
    rows, S, H, E, k = 3, 40, 32, 8, 2
    x = jax.random.normal(jax.random.PRNGKey(0), (rows * S, H))
    router = jax.random.normal(jax.random.PRNGKey(1), (H, E))
    top_e, top_p, term, counts = route_topk(x, router, k, normalize=False, sequences=rows)
    p = np.asarray(jax.nn.softmax(x @ router, axis=-1)).reshape(rows, S, E)
    chosen = np.asarray(top_e).reshape(rows, S * k)
    want = np.mean([sum(E * np.sum(chosen[r] == i) / (k * S) * p[r, :, i].mean()
                        for i in range(E)) for r in range(rows)])
    assert float(term) == pytest.approx(want, rel=1e-5)
    _, _, batchwise, counts2 = route_topk(x, router, k, normalize=False)
    flat = sum(E * np.sum(chosen == i) / (k * rows * S) * p[..., i].mean() for i in range(E))
    assert float(batchwise) == pytest.approx(flat, rel=1e-5) and abs(want - flat) > 1e-4
    np.testing.assert_array_equal(counts, counts2)
    d = dict(R.dims(TOY), router_num_experts=E, num_experts_per_tok=k)
    with jax.default_matmul_precision("highest"):
        assert float(R.route(x, router, d, rows, "float32")[3]) == pytest.approx(want, rel=1e-5)


@pytest.fixture(scope="module")
def reference():
    d, _, w = build()
    tokens, targets = batch(2, 128)
    with jax.default_matmul_precision("highest"):
        (want, counts), gr = jax.jit(jax.value_and_grad(
            lambda w: R.loss(w, d, tokens, targets), has_aux=True))(w)
        _, _, balance = jax.jit(lambda w: R.hidden(w, d, tokens))(w)
    return float(want), np.asarray(counts), float(balance), mla_tree.to_program(gr)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_loss_and_every_gradient_match_the_reference(impl, reference):
    """Rows of 128: the two-width flash kernels in interpret mode on all
    three layers, the grouped expert products on two."""
    want, counts, balance, want_g = reference
    d, model, w = build(attention_impl=impl, experts_impl=impl)
    p = mla_tree.to_program(w)
    assert jax.tree.structure(p) == jax.tree.structure(model.init(jax.random.PRNGKey(0)))
    tokens, targets = batch(2, 128)
    with jax.default_matmul_precision("highest"):
        (loss, aux), g = jax.jit(jax.value_and_grad(
            lambda p: model.loss_fn(p, tokens, targets, return_aux=True), has_aux=True))(p)
    assert abs(float(loss) - want) < 2e-5
    # the term is in the loss: summed over the expert layers at alpha
    assert float(aux["load_balance_loss"]) * 2 == pytest.approx(balance, rel=1e-4)
    assert 0.001 * balance > 1e-3
    np.testing.assert_array_equal(aux["router_counts"], counts)
    np.testing.assert_array_equal(aux["expert_load"], counts[:, 6:8])
    assert int(aux["dropped"]) == 0
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g)[0], jax.tree.leaves(want_g)):
        close(a, b, 2e-4 if impl == "pallas" else 2e-5, jax.tree_util.keystr(path))


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Sixteen experts in 8 shares of 2: the routed parts all the shares give,
    with the shared experts (which every chip computes alike) counted once,
    are the uncut reference's expert layer — and the program's share is the
    reference's share."""
    whole = R.dims(dict(TOY, n_routed_experts=16, experts_held_first=0))
    w = R.make_weights(whole, R.seed_key(7))
    lw = jax.tree.map(lambda a: a[0], w["moe"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2 * 64, 128))
    with jax.default_matmul_precision("highest"):
        uncut, counts, term = R.expert_layer(lw, whole, x, 2, "float32")
        shared = R.shared_expert(lw, x, "float32")
        parts, total = [], 0
        for first in range(0, 16, 2):
            cut = lambda a: a[first:first + 2]  # noqa: E731
            mine = dict(lw, w_gate=cut(lw["w_gate"]), w_up=cut(lw["w_up"]),
                        w_down=cut(lw["w_down"]))
            y, n, t = R.expert_layer(mine, whole, x, 2, "float32", held=(first, 2))
            np.testing.assert_array_equal(n, counts)          # every chip routes alike
            assert float(t) == float(term)
            parts.append(y)
            total += int(n[first:first + 2].sum())
            if first == 6:                                    # the program's share
                d, model, _ = build(experts_impl="xla")
                p = {"router": lw["router"],
                     "w_gate_up": jnp.concatenate([mine["w_gate"], mine["w_up"]], -1),
                     "w_down": mine["w_down"],
                     "shared_gate_up": jnp.concatenate([lw["shared_gate"], lw["shared_up"]], -1),
                     "shared_down": lw["shared_down"]}
                got, aux = model._experts(p, x.reshape(2, 64, 128))
                close(got.reshape(-1, 128), y + shared, 2e-5)
                assert float(aux["load_balance_loss"]) == pytest.approx(float(term), rel=1e-5)
        assert total == 2 * 64 * 4                            # every assignment lives somewhere
        close(sum(parts) + shared, uncut + shared, 1e-5)
        assert float(jnp.max(jnp.abs(sum(parts[:7]) - uncut))) > 1e-4


def test_remat_and_spans_leave_the_loss_alone():
    d, model, w = build(attention_impl="xla", experts_impl="xla")
    _, again, _ = build(attention_impl="xla", experts_impl="xla", remat=True)
    p = mla_tree.to_program(w)
    tokens, targets = batch(1, 64)
    grad = jax.jit(jax.grad(model.loss_fn))
    g = grad(p, tokens, targets)
    gr = jax.jit(jax.grad(again.loss_fn))(p, tokens, targets)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gr)):
        close(a, b, 1e-5)
    text = grad.lower(p, tokens, targets).as_text(debug_info=True)
    for scope in ("hybrid/attn_mla", "mla/down", "mla/up", "hybrid/dense", "hybrid/moe",
                  "moe/route", "moe/experts", "moe/shared"):
        assert scope in text, scope
    assert "hybrid/attn_win" not in text


def test_a_latent_layer_sits_beside_the_other_kinds():
    """``layer_types`` mixes the kinds; a kind no layer has has no group."""
    c = HybridDecoderConfig(
        vocab_size=64, hidden_size=64, layer_types=("linear", "latent", "full"), num_heads=2,
        num_kv_heads=1, head_dim=32, rotary_dim=16, qk_nope_dim=32, qk_rope_dim=16,
        v_head_dim=32, kv_lora_rank=16, rope_scaling=dict(YARN, original_max_position_embeddings=8),
        linear_key_heads=1, linear_value_heads=2, linear_key_dim=16, linear_value_dim=16,
        router_experts=4, top_k=2, expert_ffn=32, shared_ffn=32, attention_impl="xla",
        delta_impl="xla", experts_impl="xla")
    hash(c)                                                   # the mapping is kept hashable
    model = HybridDecoderModel(c)
    p = model.init(jax.random.PRNGKey(0))
    assert set(p["layers"]) == {"norm1", "norm2", "gdn", "attn", "mla", "moe"}
    assert p["layers"]["mla"]["w_q"].shape == (1, 64, 2 * 48)
    assert p["layers"]["mla"]["w_kva"].shape == (1, 64, 16 + 16)
    assert p["layers"]["mla"]["w_kvb"].shape == (1, 16, 2 * 64)
    tokens = jnp.arange(32, dtype=jnp.int32).reshape(1, 32) % 64
    assert np.isfinite(float(jax.jit(model.loss_fn)(p, tokens, tokens)))
    with pytest.raises(ValueError, match="latent"):
        HybridDecoderConfig(layer_types=("mla",))
