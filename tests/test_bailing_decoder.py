"""``HybridDecoderModel`` built as the ``bailing_hybrid`` block (Ling-3.0-flash)
against the plain float32 reference at a toy size: loss and every gradient
leaf, ``remat`` off and on, the XLA forms and the interpreted kernels; each of
the reference's deliberate departures (a scalar decay, the unbounded gate, no
group limit, no latent gate, no q/k norms, a bf16 state) fails the tolerance;
the shares of a router's experts add up to the uncut layer; the group-limited
choice against a brute-force one, with ties and a bias that changes the kept
groups; and with the new switches off the latent mixer, the router and a
hybrid toy's step are the parent's programs."""
import hashlib
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
from apex_tpu.models import hybrid_decoder  # noqa: E402
from apex_tpu.transformer import moe  # noqa: E402
from benchmarks.adapters import bailing_tree  # noqa: E402
from benchmarks.reference import bailing_ref as R  # noqa: E402

# the cell's cut at a toy size: published layers 1 (dense), 4 (experts) and 5 (the
# latent one), 2 heads of 128 (the kernels' width), 64 experts in 8 groups of which
# 4 stay, top 4, experts 8-15 (group 1) held
TOY = dict(
    hidden_size=64, num_hidden_layers=3, first_k_dense_replace=2, layer_group_size=6,
    num_attention_heads=2, head_dim=128, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, kv_lora_rank=32, rope_theta=6e6, short_conv_kernel_size=4,
    kda_lower_bound=-5, intermediate_size=96, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=32, num_experts=8, num_experts_per_tok=4,
    num_shared_experts=1, n_group=8, topk_group=4, norm_topk_prob=True,
    routed_scaling_factor=2.5, rms_norm_eps=1e-6, vocab_size=256, score_function="sigmoid",
    q_lora_rank=None, rope_scaling=None, kda_safe_gate=True, num_kv_heads_for_linear_attn=0,
    layers_kept=[1, 4, 5], router_num_experts=64, experts_held_first=8,
    router_bias_update_rate=1e-3)
ROWS, SEQ = 2, 160
TOL = 5e-3       # the interpreted kernels' (a decay rate's gradient sums with cancellation)
LOSS_TOL = 1e-5


def build(d, **settings):
    settings = {"attention_impl": "xla", "delta_impl": "xla", "experts_impl": "xla", **settings}
    return HybridDecoderModel(HybridDecoderConfig(**bailing_tree.config_kwargs(d, **settings)))


def batch():
    tokens = jax.random.randint(jax.random.PRNGKey(1), (ROWS, SEQ), 0, 256)
    return tokens, jnp.roll(tokens, -1, axis=1)


def leaf_gaps(got, want):
    flat = jax.tree_util.tree_leaves_with_path(got)
    return {jax.tree_util.keystr(path): float(
        jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))
        for (path, a), b in zip(flat, jax.tree.leaves(want))}


@pytest.fixture(scope="module")
def seeded():
    d = R.dims(TOY)
    w = R.make_weights(d, R.seed_key(3))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(2), (2, 64))
    return d, w, bias


@pytest.fixture(scope="module")
def reference(seeded):
    d, w, bias = seeded
    tokens, targets = batch()
    with jax.default_matmul_precision("highest"):
        (loss, counts), g = jax.jit(jax.value_and_grad(
            lambda w: R.loss(w, bias, d, tokens, targets), has_aux=True))(w)
    return float(loss), np.asarray(counts), bailing_tree.to_program(g)


def program(d, w, bias, **settings):
    model = build(d, **settings)
    tokens, targets = batch()
    (loss, aux), g = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, tokens, targets, return_aux=True, router_bias=bias),
        has_aux=True))(bailing_tree.to_program(w))
    return float(loss), aux, g


def test_dims_cut_the_published_model_as_the_cell_does(seeded):
    d = seeded[0]
    assert d["layer_types"] == ("kda", "kda", "latent")
    assert d["ffn_types"] == ("dense", "moe", "moe")
    assert d["experts_held"] == (8, 8) and d["router_num_experts"] == 64
    kw = bailing_tree.config_kwargs(d)
    assert (kw["router_groups"], kw["router_groups_kept"], kw["kda_lower_bound"]) == (8, 4, -5.0)
    assert kw["latent_qk_norm"] and kw["latent_gate"]
    with pytest.raises(ValueError, match="sigmoid"):
        R.dims(dict(TOY, kda_safe_gate=False))


@pytest.mark.parametrize("remat,impl,tol", [(False, "xla", 1e-4), (True, "pallas", TOL)],
                         ids=["kept-xla", "recomputed-kernels"])
def test_loss_and_every_gradient_leaf_match_the_reference(remat, impl, tol, seeded, reference):
    d, w, bias = seeded
    loss, counts, want = reference
    got_loss, aux, got = program(d, w, bias, remat=remat, delta_impl=impl)
    assert abs(got_loss - loss) < LOSS_TOL
    np.testing.assert_array_equal(np.asarray(aux["router_counts"]), counts)
    gaps = leaf_gaps(got, want)
    assert len(gaps) == 29 and max(gaps.values()) < tol, max(gaps.items(), key=lambda x: x[1])
    # the counters the block adds: the bound is reached to rounding and never passed;
    # the held experts are group 1 of 8, of which a token keeps 4
    assert -5.0 < float(aux["kda_log_decay_min"]) < -4.9
    hit = np.asarray(aux["router_group_hit"])
    assert hit.shape == (2,) and (0.0 < hit).all() and (hit < 1.0).all()
    assert int(aux["dropped"]) == 0


@pytest.mark.parametrize("wrong", [w for w in R.WRONG if w])
def test_each_departure_fails_the_tolerance(wrong, seeded, reference):
    """A reference that is wrong in ONE way — a scalar decay in place of the
    vector, the unbounded gate, the choice without its group limit, the latent
    layer without its gate or its q/k norms, the rule's state in bf16 — reads
    another loss than the sound one (and so than the model) by more than the
    loss's tolerance, or routes other tokens."""
    d, w, bias = seeded
    tokens, targets = batch()
    other = R.dims(TOY, wrong=wrong)
    with jax.default_matmul_precision("highest"):
        loss, _ = jax.jit(lambda w: R.loss(w, bias, other, tokens, targets))(w)
    assert abs(float(loss) - reference[0]) > LOSS_TOL, (wrong, float(loss), reference[0])


def test_the_shares_of_the_routers_experts_add_up_to_the_uncut_layer(seeded):
    """The guide's share test: the eight eight-expert shares of the toy's
    64-expert router — what eight chips of a group add before the exchange —
    with the shared expert counted once, are the uncut reference's layer."""
    d = R.dims(dict(TOY, num_experts=64, experts_held_first=0))
    k = jax.random.split(jax.random.PRNGKey(5), 3)
    lw = jax.tree.map(lambda a: a[0], R.make_weights(d, k[0])["moe"])
    x = jax.random.normal(k[1], (ROWS * SEQ, 64))
    bias = 0.05 * jax.random.normal(k[2], (64,))
    with jax.default_matmul_precision("highest"):
        whole, counts = R.expert_layer(lw, bias, d, x, "float32")
        shared = R.shared_expert(lw, x, "float32")
        total, loads = 0.0, []
        for first in range(0, 64, 8):
            share = {"router": lw["router"],
                     "w_gate_up": jnp.concatenate([lw["w_gate"], lw["w_up"]], -1)[first:first + 8],
                     "w_down": lw["w_down"][first:first + 8],
                     "shared_gate_up": jnp.concatenate([lw["shared_gate"], lw["shared_up"]], -1),
                     "shared_down": lw["shared_down"]}
            y, aux = jax.jit(lambda p, first=first: moe.dropless_moe_layer(
                p, x, top_k=4, experts_held=(first, 8), score="sigmoid", route_scale=2.5,
                router_bias=bias, shared_gate=False, groups=8, groups_kept=4, impl="xla"))(share)
            mine = dict(lw, **{n: lw[n][first:first + 8] for n in ("w_gate", "w_up", "w_down")})
            part, _ = R.expert_layer(mine, bias, d, x, "float32", held=(first, 8))
            np.testing.assert_allclose(y - shared, part, atol=2e-6)
            total = total + (y - shared)
            loads.append(np.asarray(aux["expert_load"]))
    np.testing.assert_allclose(total + shared, whole + shared, atol=5e-6)
    np.testing.assert_array_equal(np.concatenate(loads), np.asarray(counts))
    assert int(counts.sum()) == ROWS * SEQ * 4


def brute_force(s, bias, k, groups, kept):
    """The choice written out: token by token, group by group."""
    T, E = s.shape
    size = E // groups
    biased = (s + bias).astype(np.float32)
    out = np.zeros((T, k), np.int64)
    for t in range(T):
        score = [np.sort(biased[t, g * size:(g + 1) * size])[-2:].sum(dtype=np.float32)
                 for g in range(groups)]
        stay = sorted(range(groups), key=lambda g: (-score[g], g))[:kept]
        inside = [e for e in range(E) if e // size in stay]
        out[t] = sorted(inside, key=lambda e: (-biased[t, e], e))[:k]
    return out


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_group_limit_is_the_brute_force_choice(impl):
    T, E, H, k = 128, 64, 32, 4
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(keys[0], (T, H))
    router = jax.random.normal(keys[1], (H, E))
    # scores on a coarse grid: ties inside groups and between groups' sums
    router = jnp.round(router * 2) / 2
    bias = jnp.zeros((E,)).at[40:48].set(0.4)
    route = jax.jit(lambda b: moe.route_topk(
        x, router, k, score="sigmoid", bias=b, scale=2.5, groups=8, groups_kept=4, impl=impl,
        with_kept=True))
    s = np.asarray(jax.nn.sigmoid(jnp.dot(x, router, preferred_element_type=jnp.float32)))
    for b in (jnp.zeros((E,)), bias):
        top_e, top_w, aux, counts, kept = route(b)
        want = brute_force(s, np.asarray(b), k, 8, 4)
        np.testing.assert_array_equal(np.asarray(top_e), want)
        assert (np.asarray(kept).sum(-1) == 4).all() and float(aux) == 0.0
        np.testing.assert_array_equal(np.asarray(counts), np.bincount(want.ravel(), minlength=E))
        chosen = np.take_along_axis(s, want, axis=1)
        np.testing.assert_allclose(np.asarray(top_w), 2.5 * chosen / chosen.sum(-1, keepdims=True),
                                   rtol=1e-5)
    free = np.asarray(route(jnp.zeros((E,)))[4])
    pushed = np.asarray(route(bias)[4])
    assert pushed[:, 5].sum() > free[:, 5].sum()      # the bias moved group 5 in
    with pytest.raises(ValueError, match="router_groups"):
        HybridDecoderConfig(router_experts=64, router_groups=8, router_groups_kept=9)


def test_switches_off_the_router_is_todays_bit_for_bit():
    """``groups`` 1 and 1 is no argument at all: the same jaxpr, the same ids
    and weights."""
    keys = jax.random.split(jax.random.PRNGKey(9), 2)
    x, router = jax.random.normal(keys[0], (128, 32)), jax.random.normal(keys[1], (32, 16))
    plain = lambda x, r: moe.route_topk(x, r, 4, score="sigmoid", scale=2.5)  # noqa: E731
    ones = lambda x, r: moe.route_topk(x, r, 4, score="sigmoid", scale=2.5,  # noqa: E731
                                       groups=1, groups_kept=1)
    assert str(jax.make_jaxpr(plain)(x, router)) == str(jax.make_jaxpr(ones)(x, router))
    for a, b in zip(jax.jit(plain)(x, router), jax.jit(ones)(x, router)):
        np.testing.assert_array_equal(a, b)
    assert moe.route_topk(x, router, 4, with_kept=True)[4] is None


def latent_toy(**settings):
    """A toy of the latent kind as ``dsv2lite-train-8k`` builds it (no gate, no
    q/k norms, a yarn entry, softmax routing per sequence), recomputed."""
    return HybridDecoderModel(HybridDecoderConfig(
        vocab_size=256, hidden_size=64, layer_types=("latent", "latent"),
        ffn_types=("dense", "moe"), num_heads=2, num_kv_heads=2, qk_nope_dim=32, qk_rope_dim=16,
        v_head_dim=32, kv_lora_rank=24, rope_theta=1e4,
        rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
                      "mscale": 0.707, "mscale_all_dim": 0.707,
                      "original_max_position_embeddings": 64},
        router_experts=8, top_k=2, expert_ffn=32, shared_ffn=64, dense_ffn=96,
        normalize_topk=False, shared_gate=False, seq_aux=True, zero_centered_norm=False,
        remat=True, attention_impl="xla", experts_impl="xla", **settings))


# sha256 of the text the latent toy's gradient lowers to at the parent commit (PR 43,
# 7a918f2: ``python tests/test_bailing_decoder.py`` there prints it), this installation's
# jax: with its switches off the latent mixer, and the router without groups, lower to
# what they lowered to before the switches existed
PARENT_LOWERED = "120b65c133eb3f7a74c4a2cd55a2917dfaaeaa81389068d35c77bf733f71c4eb"


def lowered_hash():
    model = latent_toy()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    text = jax.jit(jax.value_and_grad(
        lambda p, a, b: model.loss_fn(p, a, b, return_aux=True), has_aux=True)).lower(
            params, tokens, tokens).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def test_switches_off_the_latent_mixer_lowers_to_the_parents_text():
    assert latent_toy().config == latent_toy(latent_qk_norm=False, latent_gate=False,
                                             router_groups=1, router_groups_kept=1).config
    params = jax.eval_shape(latent_toy().init, jax.random.PRNGKey(0))
    assert set(params["layers"]["mla"]) == {"w_q", "w_kva", "kv_norm", "w_kvb", "w_o"}
    assert lowered_hash() == PARENT_LOWERED
    # and the new kind's results are among what ``remat`` keeps of a mixer half
    assert set(hybrid_decoder.MIXER_SAVED) >= {"kda_o", "kda_s0", "gdn_o", "flash_o"}


def test_the_switches_add_their_leaves_and_the_new_kind_its_group():
    model = build(R.dims(TOY))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert set(params["layers"]["mla"]) == {"w_q", "w_kva", "kv_norm", "w_kvb", "w_o",
                                            "q_norm", "k_norm", "w_gate"}
    assert set(params["layers"]["kda"]) == {"w_qkv", "w_f", "w_g", "w_b", "conv_w", "A_log",
                                            "dt_bias", "norm_w", "w_o"}
    assert params["layers"]["kda"]["w_qkv"].shape == (2, 64, 3 * 256)
    assert params["layers"]["mla"]["w_gate"].shape == (1, 64, 2)
    assert params["layers"]["kda"]["A_log"].dtype == jnp.float32
    assert {"A_log", "dt_bias"} <= set(model.float32_params)
    with pytest.raises(ValueError, match="'kda'"):
        HybridDecoderConfig(layer_types=("delta",))


if __name__ == "__main__":
    print(lowered_hash())
