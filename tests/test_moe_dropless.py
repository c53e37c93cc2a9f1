"""The dropless expert layer against the plain reference: uneven routing,
nothing dropped, the block-after-block path, and the share test — the
partial results of all shares, the shared expert counted once, add up to the
uncut layer's result; and the second router: sigmoid scores under a
selection bias, its weights, its update and its own share test."""
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

H, F, E, K = 128, 128, 16, 4
D = {"router_num_experts": E, "num_experts_per_tok": K, "norm_topk_prob": True,
     "experts_held": (0, E)}


def weights(seed=0, skew=None):
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 10))
    n = lambda *s: 0.05 * jax.random.normal(next(k), s)  # noqa: E731
    w = {"router": n(H, E), "w_gate": n(E, H, F), "w_up": n(E, H, F), "w_down": n(E, F, H),
         "shared_gate": n(H, F), "shared_up": n(H, F), "shared_down": n(F, H), "shared_mix": n(H)}
    if skew is not None:
        w["router"] = w["router"] + skew
    return w


def program(w, first=0, count=E):
    cut = lambda a: a[first:first + count]  # noqa: E731
    return {"router": w["router"],
            "w_gate_up": jnp.concatenate([cut(w["w_gate"]), cut(w["w_up"])], -1),
            "w_down": cut(w["w_down"]),
            "shared_gate_up": jnp.concatenate([w["shared_gate"], w["shared_up"]], -1),
            "shared_down": w["shared_down"], "shared_mix": w["shared_mix"]}


def reference(w, x, first=0, count=E):
    lw = dict(w, **{n: w[n][first:first + count] for n in ("w_gate", "w_up", "w_down")})
    y, aux, load = R.expert_layer(lw, D, x, "float32", held=(first, count))
    return y + R.shared_expert(w, x, "float32"), aux, load



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
        y, aux = f(program(w), x)
        want, want_aux, want_load = reference(w, flat)
        load = np.asarray(aux["expert_load"])
        assert load[3] == 0 and load[5] >= 0.9 * flat.shape[0] and load.sum() == K * flat.shape[0]
        assert int(aux["dropped"]) == 0
        np.testing.assert_array_equal(load, np.asarray(want_load))
        np.testing.assert_allclose(y.reshape(-1, H), want, atol=2e-5 * float(jnp.max(jnp.abs(want))))
        np.testing.assert_allclose(aux["load_balance_loss"], want_aux, rtol=1e-5)
        r = jax.random.normal(jax.random.PRNGKey(8), want.shape)
        loss = lambda p, x: jnp.sum(f(p, x)[0].reshape(-1, H) * r) + f(p, x)[1]["load_balance_loss"]  # noqa: E731
        ref_loss = lambda w, x: (lambda o: jnp.sum(o[0] * r) + o[1])(reference(w, x))  # noqa: E731
        gp, gx = jax.grad(loss, argnums=(0, 1))(program(w), x)
        gw, gxr = jax.grad(ref_loss, argnums=(0, 1))(w, flat)
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
        y, aux = moe.dropless_moe_layer(program(w, 0, 4), x, top_k=K, experts_held=(0, 4), impl=impl)
        assert int(aux["expert_load"].sum()) == 0
        np.testing.assert_allclose(y, R.shared_expert(w, x, "float32"), atol=1e-5)
        g = jax.grad(lambda p: jnp.sum(moe.dropless_moe_layer(
            p, x, top_k=K, experts_held=(0, 4), impl=impl)[0]))(program(w, 0, 4))
        assert float(jnp.max(jnp.abs(g["w_gate_up"]))) == 0.0
        assert float(jnp.max(jnp.abs(g["w_down"]))) == 0.0


# --- the sigmoid router with a selection bias ---------------------------------

SIG = {"router_num_experts": E, "num_experts_per_tok": K, "route_norm": True,
       "route_scale": 2.826, "experts_held": (0, E)}


def test_bias_moves_the_selection_and_not_the_weights():
    x = jax.random.normal(jax.random.PRNGKey(2), (64, H))
    router = weights()["router"]
    bias = jnp.zeros((E,)).at[7].set(10.0).at[2].set(-10.0)   # always 7, never 2
    plain_e, plain_w, aux, _ = moe.route_topk(x, router, K, score="sigmoid", scale=2.826)
    top_e, top_w, _, counts = moe.route_topk(x, router, K, score="sigmoid", bias=bias, scale=2.826)
    assert float(aux) == 0.0                               # balanced by the bias, not a loss
    assert int(counts[7]) == 64 and int(counts[2]) == 0 and int(counts.sum()) == 64 * K
    s = jax.nn.sigmoid(jnp.dot(x, router))
    chosen = jnp.take_along_axis(s, top_e, -1)
    # the weights are the scores themselves, renormalised and scaled: no bias in them
    np.testing.assert_allclose(top_w, chosen / chosen.sum(-1, keepdims=True) * 2.826, rtol=1e-6)
    np.testing.assert_allclose(top_w.sum(-1), 2.826, rtol=1e-6)
    raw = moe.route_topk(x, router, K, score="sigmoid", bias=bias, normalize=False)[1]
    np.testing.assert_allclose(raw, chosen, rtol=1e-6)     # route_scale 1, no renormalisation
    # a zero bias is no bias; the reference agrees on ids, weights and counts
    zero = moe.route_topk(x, router, K, score="sigmoid", bias=jnp.zeros((E,)), scale=2.826)
    np.testing.assert_array_equal(zero[0], plain_e)
    np.testing.assert_allclose(zero[1], plain_w, rtol=1e-6)
    ref_e, ref_w, ref_counts = A.route(x, router, bias, SIG, "float32")
    np.testing.assert_array_equal(top_e, ref_e)
    np.testing.assert_allclose(top_w, ref_w, rtol=1e-5)
    np.testing.assert_array_equal(counts, ref_counts)
    # no gradient reaches the bias; the router's is the weights' alone
    g = jax.grad(lambda b: jnp.sum(moe.route_topk(x, router, K, score="sigmoid", bias=b)[1]))(bias)
    assert float(jnp.max(jnp.abs(g))) == 0.0


def test_bias_update_is_a_signed_step_toward_the_mean_load():
    counts = jnp.asarray([[0, 4, 8, 4], [5, 5, 5, 5]], jnp.int32)
    bias = jnp.asarray([[0.0, 0.5, 0.0, -0.5], [0.1, 0.2, 0.3, 0.4]])
    got = moe.router_bias_update(bias, counts, 0.001)
    np.testing.assert_allclose(got, [[0.001, 0.5, -0.001, -0.5], [0.1, 0.2, 0.3, 0.4]], atol=1e-7)
    np.testing.assert_allclose(got, A.bias_update(bias, counts.astype(jnp.float32),
                                                  {"load_balance_coeff": 0.001}), atol=1e-7)


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
        gp = jax.grad(lambda p: jnp.sum(moe.dropless_moe_layer(
            p, x, top_k=K, impl=impl, score="sigmoid", route_scale=2.826,
            router_bias=bias, shared_gate=False)[0].reshape(-1, H) * r))(sigmoid_program(w))
        gw = sigmoid_program(jax.grad(lambda w: jnp.sum(ref(w, flat) * r))(w))
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


@pytest.mark.parametrize("N", [768, 2816, 1024])
def test_grouped_dw_writes_every_column(N):
    """``moe_gmm_dw`` takes column blocks that divide N: at 2 x 1,408 = 2,816
    (= 5.5 x 512) and at 768 a 512-block left the last 256 columns of every
    expert's gradient unwritten, silently zero."""
    from apex_tpu.ops.pallas import grouped_matmul as gk
    M, K, E = 4 * gk.TM, 128, 3
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K))
    dy = jax.random.normal(jax.random.PRNGKey(1), (M, N))
    tile_expert = jnp.array([0, 0, 2, 2], jnp.int32)
    n_used = jnp.array([3], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = gk.moe_gmm_dw(x, dy, tile_expert, n_used, E, interpret=True)
        per_tile = jnp.einsum("tmk,tmn->tkn", x.reshape(4, gk.TM, K)[:3],
                              dy.reshape(4, gk.TM, N)[:3])
    want = jax.ops.segment_sum(per_tile, tile_expert[:3], num_segments=E)
    np.testing.assert_allclose(got, want, atol=1e-3)
    assert float(jnp.min(jnp.max(jnp.abs(got[0]), axis=0))) > 0.1     # no column left at zero
    assert float(jnp.max(jnp.abs(got[1]))) == 0.0                     # an expert with no tile


# --- the row movements as kernels (``ops/pallas/expert_rows``) ------------------

def _movement_operands(tokens, hidden, held, width, dtype, skew=None, seed=30):
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    n = lambda *s: (0.05 * jax.random.normal(next(k), s)).astype(dtype)  # noqa: E731
    p = {"router": n(hidden, width), "w_gate_up": n(held[1], hidden, 2 * F),
         "w_down": n(held[1], F, hidden), "shared_gate_up": n(hidden, 2 * F),
         "shared_down": n(F, hidden), "shared_mix": n(hidden)}
    if skew is not None:
        p["router"] = (p["router"].astype(jnp.float32) + skew).astype(dtype)
    x = jnp.abs(jax.random.normal(next(k), (tokens, hidden))).astype(dtype)
    return p, x


def _layer_and_grads(impl, p, x, top_k, held):
    r = jax.random.normal(jax.random.PRNGKey(31), x.shape)

    def loss(p, x):
        y, aux = moe.dropless_moe_layer(p, x, top_k=top_k, experts_held=held, impl=impl)
        return jnp.sum(y.astype(jnp.float32) * r), (y, aux)
    (_, (y, aux)), (gp, gx) = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(p, x)
    return y, aux, gp, gx


MOVEMENT_CASES = {
    # tokens, hidden, top_k, (first, count), router width, block rows (None: the layer's own), skew
    "uneven routing with an empty expert": (192, 128, 4, (0, 16), 16, None, {5: 0.3, 3: -0.3}),
    "every assignment of every token local": (160, 128, 4, (0, 8), 8, None, {}),
    "no local assignment at all": (96, 128, 4, (0, 4), 16, None, {0: -1.0, 1: -1.0, 2: -1.0, 3: -1.0}),
    "a routing that fills a second block and a third": (130, 256, 2, (2, 6), 8, 128, {}),
    "trinity-train-8k's 8 onto 16 of 128": (256, 128, 8, (16, 16), 128, None, {}),
    "dsv2lite-train-8k's 6 onto 8 of 64": (300, 256, 6, (0, 8), 64, None, {}),
    "q3next-train-8k's 10 onto 32 of 512": (256, 128, 10, (64, 32), 512, None, {}),
}


@pytest.mark.parametrize("case", list(MOVEMENT_CASES))
def test_row_movement_kernels_match_the_xla_composition(case, monkeypatch):
    """``moe_rows_gather`` / ``moe_rows_combine`` (interpreted) against XLA's
    gathers: the layer's output and every gradient — tokens, router (through
    the weights), both expert matrices."""
    tokens, hidden, top_k, held, width, block, skews = MOVEMENT_CASES[case]
    if block is not None:
        monkeypatch.setattr(moe, "dropless_block_rows", lambda *_: block)
    skew = jnp.zeros((hidden, width))
    for column, by in skews.items():
        skew = skew.at[:, column].set(by)
    p, x = _movement_operands(tokens, hidden, held, width, jnp.float32, skew)
    with jax.default_matmul_precision("highest"):
        want = _layer_and_grads("xla", p, x, top_k, held)
        got = _layer_and_grads("pallas", p, x, top_k, held)
    load = np.asarray(want[1]["expert_load"])
    np.testing.assert_array_equal(got[1]["expert_load"], load)
    assert int(got[1]["dropped"]) == 0
    if case.startswith("no local"):
        assert load.sum() == 0
    if case.startswith("every"):
        assert load.sum() == tokens * top_k
    if "empty expert" in case:
        assert load[3] == 0 and load[5] > 0.9 * tokens
    if "block" in case:
        assert load.sum() + held[1] * 128 > 2 * block     # more rows than two blocks hold
    close = lambda a, b, name: np.testing.assert_allclose(  # noqa: E731
        a, b, err_msg=name, atol=2e-5 * float(jnp.max(jnp.abs(b))) + 1e-9)
    close(got[0], want[0], "y")
    close(got[3], want[3], "dx")
    for name in want[2]:
        close(got[2][name], want[2][name], name)


def _one_block_move(tokens, top_k, held, width, seed=40):
    from apex_tpu.ops.pallas import grouped_matmul as gk
    x = jax.random.normal(jax.random.PRNGKey(seed), (tokens, H))
    router = 0.3 * jax.random.normal(jax.random.PRNGKey(seed + 1), (H, width))
    top_e, top_p, _, counts = moe.route_topk(x, router, top_k)
    rows = moe.dropless_block_rows(tokens, top_k, held, width)
    plan = moe.dropless_plan(top_e, counts, (0, held), rows, gk.TM)
    return moe._block_move(plan, 0, rows), top_p, rows


def test_bf16_combine_is_the_float32_sum_rounded_once():
    """bf16 rows under weights that bf16 holds exactly: every product is exact
    in float32 either way, so the kernel's sum (on the MXU, the weights as bf16
    parts) and XLA's ``einsum`` over the float32 ``picked`` round the same
    float32 number once — bit for bit; and with weights of 24 bits the kernel
    stays within one bf16 step of the float32 sum."""
    move, top_p, rows = _one_block_move(192, 4, 8, 16)
    # a bf16 row packs its two halves into one word each pair: 256 wide
    y = jax.random.normal(jax.random.PRNGKey(42), (rows, 2 * H)).astype(jnp.bfloat16)
    exact = top_p.astype(jnp.bfloat16).astype(jnp.float32)
    got = moe._tokens_from_rows(y, exact, move, "pallas")
    want = moe._tokens_from_rows(y, exact, move, "xla")
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    ones = moe._rows_bwd("pallas", move, y)[0]               # the unweighted sum
    np.testing.assert_array_equal(np.asarray(ones, np.float32),
                                  np.asarray(moe._rows_bwd("xla", move, y)[0], np.float32))
    got = np.asarray(moe._tokens_from_rows(y, top_p, move, "pallas"), np.float32)
    full = np.einsum("tkh,tk->th", np.where(np.asarray(move["sel"])[..., None],
                                            np.asarray(y, np.float64)[np.asarray(move["pos"])], 0),
                     np.asarray(top_p, np.float64))
    assert np.max(np.abs(got - full) / (np.abs(full) + 1e-3)) < 2 ** -8


def test_gather_kernel_stops_at_the_tiles_in_use():
    """``n_used`` = 0: zeros and nothing fetched (the source may hold
    anything); rows scaled by 0 are zeros; the scale is applied in float32
    and rounded once; the dots are the unscaled rows'."""
    from apex_tpu.ops.pallas import expert_rows as rk
    W = 2 * H
    x = jax.random.normal(jax.random.PRNGKey(50), (64, W)).astype(jnp.bfloat16)
    src = (jnp.arange(256) % 64).astype(jnp.int32)
    kept = jnp.arange(256) % 3 != 0
    scale = jnp.where(kept, jax.random.normal(jax.random.PRNGKey(51), (256,)), 0.0)
    other = jax.random.normal(jax.random.PRNGKey(52), (256, W)).astype(jnp.bfloat16)
    call = lambda used, source: rk.moe_rows_gather(  # noqa: E731
        rk.as_groups(source), src, scale, jnp.array([used], jnp.int32), other,
        width=W, dtype=jnp.bfloat16, interpret=True)
    rows, dots = call(0, jnp.full_like(x, jnp.nan))
    assert float(jnp.max(jnp.abs(rows.astype(jnp.float32)))) == 0.0 and float(jnp.max(jnp.abs(dots))) == 0.0
    rows, dots = call(1, x)
    taken = jnp.where(kept[:, None], x[src].astype(jnp.float32), 0.0)
    used = (jnp.arange(256) < 128)[:, None]
    want = jnp.where(used, taken * scale[:, None], 0.0).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(rows, np.float32), np.asarray(want, np.float32))
    want_dots = jnp.where(used[:, 0], jnp.sum(taken * other.astype(jnp.float32), -1), 0.0)
    np.testing.assert_allclose(dots, want_dots, atol=1e-4)


def test_plan_lists_every_local_assignment_once_in_token_order():
    from apex_tpu.ops.pallas import expert_rows as rk
    move, _, rows = _one_block_move(300, 6, 8, 64)
    count, listed, rank = (np.asarray(move[n]) for n in ("tile_count", "tile_rows", "rank"))
    pos, sel = np.asarray(move["pos"]), np.asarray(move["sel"])
    assert listed.shape == (3, rk.list_length(6)) and rank.shape == (3 * rk.TT, 6)
    assert count.sum() == sel.sum() == np.asarray(move["row_valid"]).sum()
    for tile in range(3):
        mine = slice(tile * rk.TT, min((tile + 1) * rk.TT, 300))
        np.testing.assert_array_equal(listed[tile, :count[tile]], pos[mine][sel[mine]])
        np.testing.assert_array_equal(rank[mine][sel[mine]], np.arange(count[tile]))
    assert (rank[:300][~sel] == -1).all() and (rank[300:] == -1).all()


def test_bf16_layer_on_the_kernels_matches_the_xla_composition(top_k=6, held=(0, 8), width=64):
    """bf16 operands at 256 wide (a row's group: one line of packed words):
    the packed path of all three kernels, forward and every gradient, within
    bf16's rounding of the XLA composition."""
    p, x = _movement_operands(256, 256, held, width, jnp.bfloat16)
    want = _layer_and_grads("xla", p, x, top_k, held)
    got = _layer_and_grads("pallas", p, x, top_k, held)
    np.testing.assert_array_equal(got[1]["expert_load"], want[1]["expert_load"])
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    close = lambda a, b, name: np.testing.assert_allclose(  # noqa: E731
        f32(a), f32(b), err_msg=name, atol=2e-2 * float(np.max(np.abs(f32(b)))) + 1e-9)
    close(got[0], want[0], "y")
    close(got[3], want[3], "dx")
    for name in want[2]:
        close(got[2][name], want[2][name], name)


@pytest.mark.parametrize("width, dtype, takes, moves_at", [
    (2048, jnp.bfloat16, "pallas", 2048), (1024, jnp.float32, "pallas", 1024),
    (1024, jnp.bfloat16, "xla", 2048), (512, jnp.float32, "xla", 1024),
    # nemotron3-train-8k's rows: 10.5 lines of 128 words move as 16
    (2688, jnp.bfloat16, "pallas", 4096), (2688, jnp.float32, "pallas", 3072)])
def test_compiled_movements_keep_xla_at_widths_the_row_dma_cannot_take(
        width, dtype, takes, moves_at, monkeypatch):
    """Compiled, a row's group is whole tiles of eight lines: a width between
    two such moves at the next one, zeros in the columns added, where that is
    under twice its own; narrower widths keep XLA's movements under
    ``impl="pallas"`` too (the grouped products take them) instead of
    raising."""
    monkeypatch.setattr(moe._backend, "interpret_mode", lambda: False)
    a = jax.ShapeDtypeStruct((256, width), dtype)
    assert moe._rows_width(a) == moves_at
    assert moe._rows_impl("pallas", a) == takes
    assert moe._rows_impl("xla", a) == "xla"


def test_rows_of_a_width_between_two_the_kernels_take_move_widened(top_k=6, held=(0, 8)):
    """bf16 rows of 384 (one and a half lines of packed words, as 2,688 is
    10.5 compiled): the movements run on the kernels at 512 with zeros in the
    added columns, forward and every gradient, and nothing of the padding
    reaches a result."""
    p, x = _movement_operands(256, 384, held, 64, jnp.bfloat16)
    assert moe._rows_width(x) == 512 and moe._rows_impl("pallas", x) == "pallas"
    names = lambda impl: str(jax.make_jaxpr(  # noqa: E731
        lambda p, x: _layer_and_grads(impl, p, x, top_k, held)[0])(p, x))
    assert "moe_rows_gather" in names("pallas") and "moe_rows_combine" in names("pallas")
    assert "moe_rows" not in names("xla")
    want = _layer_and_grads("xla", p, x, top_k, held)
    got = _layer_and_grads("pallas", p, x, top_k, held)
    np.testing.assert_array_equal(got[1]["expert_load"], want[1]["expert_load"])
    assert got[0].shape == want[0].shape == (256, 384) and got[3].shape == (256, 384)
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    close = lambda a, b, name: np.testing.assert_allclose(  # noqa: E731
        f32(a), f32(b), err_msg=name, atol=2e-2 * float(np.max(np.abs(f32(b)))) + 1e-9)
    close(got[0], want[0], "y")
    close(got[3], want[3], "dx")
    for name in want[2]:
        close(got[2][name], want[2][name], name)


@pytest.mark.parametrize("K,N", [(256, 1856), (1856, 256)])
def test_grouped_products_take_a_width_of_fourteen_and_a_half_lane_tiles(K, N):
    """1,856 = 14.5 x 128 as the output width and as the contracted one: all
    three ``moe_gmm*`` kernels at the width itself (whole-matrix blocks, no
    padding in HBM), no result column lost, none of ``dw`` left at zero."""
    from apex_tpu.ops.pallas import grouped_matmul as gk
    M, E = 4 * gk.TM, 3
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    x, dy = jax.random.normal(k[0], (M, K)), jax.random.normal(k[1], (M, N))
    w = jax.random.normal(k[2], (E, K, N))
    tile_expert, n_used = jnp.array([0, 0, 2, 2], jnp.int32), jnp.array([3], jnp.int32)
    assert moe._gmm_shapes_ok(x, w) and moe._gmm_shapes_ok(dy, jnp.swapaxes(w, 1, 2))
    assert not moe._gmm_shapes_ok(x[:, :200], w[:, :200])
    used = (jnp.arange(4) < 3)[:, None, None]
    with jax.default_matmul_precision("highest"):
        out = gk.moe_gmm(x, w, tile_expert, n_used, interpret=True)
        dx = gk.moe_gmm_dx(dy, w, tile_expert, n_used, interpret=True)
        dw = gk.moe_gmm_dw(x, dy, tile_expert, n_used, E, interpret=True)
        xt, dyt = x.reshape(4, gk.TM, K), jnp.where(used, dy.reshape(4, gk.TM, N), 0)
        want = jnp.where(used, jnp.einsum("tmk,tkn->tmn", xt, w[tile_expert]), 0)
        want_dx = jnp.einsum("tmn,tkn->tmk", dyt, w[tile_expert])
        want_dw = jax.ops.segment_sum(jnp.einsum("tmk,tmn->tkn", xt, dyt), tile_expert,
                                      num_segments=E)
    assert out.shape == (M, N) and dx.shape == (M, K) and dw.shape == (E, K, N)
    np.testing.assert_allclose(out, want.reshape(M, N), atol=1e-3)
    np.testing.assert_allclose(dx, want_dx.reshape(M, K), atol=1e-3)
    np.testing.assert_allclose(dw, want_dw, atol=1e-3)
    assert float(jnp.min(jnp.max(jnp.abs(out[:gk.TM]), axis=0))) > 0.1   # every result column
    assert float(jnp.min(jnp.max(jnp.abs(dw[0]), axis=0))) > 0.1         # every column of dw
    assert float(jnp.max(jnp.abs(dw[1]))) == 0.0                         # an expert with no tile


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
        (got, aux), g = jax.value_and_grad(layer, argnums=(0, 1), has_aux=True)(w, x)
        (want, counts), g_want = jax.value_and_grad(ref, argnums=(0, 1), has_aux=True)(w, x)
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


# --- routing without a sort: the rounds against ``top_k``, the counted plan against the sort -----

def _sorted_plan(top_e, counts, experts_held, block_rows, tile):
    """``dropless_plan`` as it stood while it sorted: a stable ``argsort`` of
    all T k assignments by held expert. The oracle of the counted plan."""
    from apex_tpu.ops.pallas import expert_rows as rk
    first, count = experts_held
    T, k = top_e.shape
    N = T * k
    worst = T * min(k, count) + count * tile
    rows = -(-worst // block_rows) * block_rows
    local = (top_e >= first) & (top_e < first + count)
    key = jnp.where(local, top_e - first, count).reshape(N)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)   # sorted place -> assignment
    place = jnp.zeros((N,), jnp.int32).at[order].set(jnp.arange(N, dtype=jnp.int32))
    held = jax.lax.dynamic_slice(counts, (first,), (count,))
    start = jnp.cumsum(held) - held                           # first sorted place
    tiles_of = -(-held // tile)
    tile_end = jnp.cumsum(tiles_of)
    tile_start = tile_end - tiles_of
    n_used = tile_end[-1]
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(rows // tile, dtype=jnp.int32), side="right"),
        count - 1).astype(jnp.int32)
    r = jnp.arange(rows, dtype=jnp.int32)
    e = tile_expert[r // tile]
    within = r - tile_start[e] * tile
    row_valid = (r // tile < n_used) & (within < held[e])
    row_assign = order[jnp.clip(start[e] + within, 0, N - 1)]
    e_of = jnp.minimum(key, count - 1)
    pos = (tile_start[e_of] * tile + place - start[e_of]).reshape(T, k)
    token_tiles = -(-T // rk.TT)
    by_tile = lambda a: jnp.pad(a, ((0, token_tiles * rk.TT - T), (0, 0))  # noqa: E731
                                ).reshape(token_tiles, rk.TT * k)
    listed = by_tile(local)
    upto = jnp.cumsum(listed, axis=1, dtype=jnp.int32)
    rank = jnp.where(listed, upto - 1, -1)
    length = rk.list_length(min(k, count))
    slot = jnp.where(listed, jnp.arange(token_tiles, dtype=jnp.int32)[:, None] * length + rank,
                     token_tiles * length)
    tile_rows = jnp.zeros((token_tiles * length,), jnp.int32).at[slot.reshape(-1)].set(
        by_tile(pos).reshape(-1), mode="drop", unique_indices=True)
    return {"tile_expert": tile_expert, "n_used": n_used.astype(jnp.int32),
            "row_token": row_assign // k, "row_assign": row_assign,
            "row_valid": row_valid, "pos": pos, "local": local,
            "tile_rows": tile_rows.reshape(token_tiles, length), "tile_count": upto[:, -1],
            "rank": rank.reshape(-1, k)}


PLAN_CASES = {
    # tokens, router width, top_k, (first, count), block rows (None: the layer's own), skew by expert
    "trinity-train-8k's 8 onto 16 of 128": (256, 128, 8, (16, 16), None, {}),
    "dsv2lite-train-8k's 6 onto 8 of 64": (256, 64, 6, (0, 8), None, {}),
    "q3next-train-8k's 10 onto 32 of 512": (256, 512, 10, (64, 32), None, {}),
    "nemotron3-train-8k's 6 onto 8 of 128": (256, 128, 6, (0, 8), None, {}),
    "no local assignment": (192, 16, 4, (0, 4), None, {0: -9.0, 1: -9.0, 2: -9.0, 3: -9.0}),
    "every assignment local": (160, 8, 4, (0, 8), None, {}),
    "one expert taking all": (200, 16, 4, (0, 8), None, {5: 9.0}),
    "the held experts not the first": (256, 32, 4, (20, 8), None, {}),
    "tokens that fill no whole tile": (300, 64, 6, (8, 8), None, {}),
    "a load that runs a second block": (130, 8, 2, (2, 6), 128, {}),
    "an expert twice in one token": (192, 16, 4, (4, 8), None, "twice"),
}


@pytest.mark.parametrize("case", list(PLAN_CASES))
def test_counted_plan_is_the_sorted_plan(case):
    """Equal to the stable sort's plan on every entry that ``row_valid`` /
    ``local`` admits, and in everything that rests on the counts alone."""
    from apex_tpu.ops.pallas import grouped_matmul as gk
    tokens, width, k, held, block, skew = PLAN_CASES[case]
    score = jax.random.normal(jax.random.PRNGKey(70), (tokens, width))
    if skew == "twice":       # no router's answer: what the plan's contract still covers
        top_e = jax.random.randint(jax.random.PRNGKey(71), (tokens, k), 0, width)
        top_e = top_e.at[:, 2].set(top_e[:, 0])
    else:
        for e, by in skew.items():
            score = score.at[:, e].add(by)
        top_e = jax.lax.top_k(score, k)[1]
    counts = jnp.bincount(top_e.reshape(-1), length=width).astype(jnp.int32)
    rows = block or moe.dropless_block_rows(tokens, k, held[1], width)
    got = jax.jit(lambda e, c: moe.dropless_plan(e, c, held, rows, gk.TM))(top_e, counts)
    want = jax.jit(lambda e, c: _sorted_plan(e, c, held, rows, gk.TM))(top_e, counts)
    assert set(got) == set(want)
    for name in want:
        assert got[name].shape == want[name].shape and got[name].dtype == want[name].dtype, name
    for name in ("tile_expert", "n_used", "row_valid", "local", "tile_rows", "tile_count", "rank"):
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)
    local, valid = np.asarray(want["local"]), np.asarray(want["row_valid"])
    np.testing.assert_array_equal(np.asarray(got["pos"])[local], np.asarray(want["pos"])[local])
    for name in ("row_assign", "row_token"):
        np.testing.assert_array_equal(np.asarray(got[name])[valid], np.asarray(want[name])[valid],
                                      err_msg=name)
        assert (np.asarray(got[name])[~valid] == 0).all()      # in range, and weighs nothing
    assert local.sum() == valid.sum()
    if case == "no local assignment":
        assert local.sum() == 0 and int(got["n_used"]) == 0
    if case == "every assignment local":
        assert local.all()
    if case == "one expert taking all":
        assert int(counts[5]) == tokens
    if case == "a load that runs a second block":
        assert int(np.asarray(got["pos"])[local].max()) >= rows


def _sorted_route(x, router, k, score, bias):
    """The ids and the weights as ``jax.lax.top_k`` gives them."""
    logits = jnp.dot(x, router, preferred_element_type=jnp.float32)
    p = jax.nn.softmax(logits, -1) if score == "softmax" else jax.nn.sigmoid(logits)
    top_e = jax.lax.top_k(p if bias is None else p + bias, k)[1]
    return top_e, jnp.take_along_axis(p, top_e, axis=-1)


ROUND_CASES = {
    # score, bias, top_k, tied columns
    "softmax": ("softmax", False, K, False),
    "softmax under a bias": ("softmax", True, K, False),
    "sigmoid": ("sigmoid", False, K, False),
    "sigmoid under a bias": ("sigmoid", True, K, False),
    "tied scores, softmax": ("softmax", False, K, True),
    "tied scores, sigmoid under a bias": ("sigmoid", True, K, True),
    "the top one": ("softmax", False, 1, False),
    "every expert": ("sigmoid", True, E, False),
}


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("case", list(ROUND_CASES))
def test_rounds_choose_what_top_k_chooses(case, impl):
    """The ids in ``top_k``'s order, the lower index among equal scores
    first, and the weights gathered at them."""
    score, biased, k, tied = ROUND_CASES[case]
    x = jax.random.normal(jax.random.PRNGKey(72), (256, H))
    router = 0.3 * jax.random.normal(jax.random.PRNGKey(73), (H, E))
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(74), (E,)) if biased else None
    if tied:                  # equal columns in threes: equal scores, to the bit
        router = router[:, jnp.arange(E) // 3 * 3]
        bias = None if bias is None else bias[jnp.arange(E) // 3 * 3]
    want_e, want_p = _sorted_route(x, router, k, score, bias)
    got_e, got_p, _, counts = moe.route_topk(x, router, k, score=score, bias=bias,
                                             normalize=False, impl=impl)
    assert got_e.dtype == jnp.int32 and got_e.shape == (256, k)
    program = str(jax.make_jaxpr(lambda x: moe.route_topk(x, router, k, impl=impl))(x))
    assert ("moe_top_rounds" in program) == (impl == "pallas")
    np.testing.assert_array_equal(got_e, want_e)
    np.testing.assert_array_equal(got_p, want_p)
    np.testing.assert_array_equal(counts, jnp.bincount(want_e.reshape(-1), length=E))
    if tied:
        first_of_three = np.asarray(want_e) % 3 == 0
        assert first_of_three[:, 0].all() and 0 < first_of_three.mean() < 1
    # the weights' cotangent reaches the router as the gather's does
    r = jax.random.normal(jax.random.PRNGKey(78), want_p.shape)
    got_g = jax.grad(lambda w: jnp.sum(r * moe.route_topk(
        x, w, k, score=score, bias=bias, normalize=False, impl=impl)[1]))(router)
    want_g = jax.grad(lambda w: jnp.sum(r * _sorted_route(x, w, k, score, bias)[1]))(router)
    np.testing.assert_allclose(got_g, want_g, atol=1e-5 * float(jnp.max(jnp.abs(want_g))))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("score", ["softmax", "sigmoid under a bias"])
def test_layer_and_gradients_are_those_of_the_sorting_layer(score, impl, monkeypatch):
    """The layer on the rounds and the counted plan against the layer as it
    sorted (``jax.lax.top_k`` and the sorted plan in their place): the output,
    ``x``'s gradient, the router's and every expert leaf's."""
    from apex_tpu.ops.pallas import top_rounds as tr
    w = weights(seed=5, skew=jnp.zeros((E,)).at[2].set(0.3))
    x = jax.random.normal(jax.random.PRNGKey(75), (2, 128, H))
    r = jax.random.normal(jax.random.PRNGKey(76), x.shape)
    first, count = 4, 8
    kw = dict(top_k=K, experts_held=(first, count), impl=impl)
    if score != "softmax":
        kw.update(score="sigmoid", route_scale=2.5,
                  router_bias=0.1 * jax.random.normal(jax.random.PRNGKey(77), (E,)))

    def grads():
        def loss(p, x):
            y, aux = moe.dropless_moe_layer(p, x, **kw)
            return jnp.sum(y * r) + 0.01 * aux["load_balance_loss"], (y, aux)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(
            program(w, first, count), x)

    (_, (y, aux)), (gp, gx) = grads()
    monkeypatch.setattr(tr, "top_rounds", lambda s, k: jax.lax.top_k(s, k)[1])
    monkeypatch.setattr(tr, "moe_top_rounds",
                        lambda s, bias, k, interpret: jax.lax.top_k(s.T + bias, k)[1].T)
    monkeypatch.setattr(moe, "dropless_plan", _sorted_plan)
    (_, (y0, aux0)), (gp0, gx0) = grads()
    assert int(aux["dropped"]) == 0
    np.testing.assert_array_equal(aux["expert_load"], aux0["expert_load"])
    np.testing.assert_allclose(y, y0, atol=2e-6)
    np.testing.assert_allclose(gx, gx0, atol=2e-6)
    for name in gp0:
        np.testing.assert_allclose(gp[name], gp0[name], atol=2e-6, err_msg=name)
    assert float(jnp.max(jnp.abs(gp0["router"]))) > 0
