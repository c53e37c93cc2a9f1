"""The expert layer's routers: sigmoid scores under a selection bias, the
bias's update, the rounds of a row maximum against ``jax.lax.top_k``, the
counted plan against the plan as it stood while it sorted, and the layer on
both against the layer that sorts."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu.ops.pallas import expert_rows as rk  # noqa: E402
from apex_tpu.ops.pallas import grouped_matmul as gk  # noqa: E402
from apex_tpu.ops.pallas import top_rounds as tr  # noqa: E402
from apex_tpu.transformer import moe  # noqa: E402
from benchmarks.reference import afmoe_ref as A  # noqa: E402
from moe_toy import E, H, K, SIG, program, weights  # noqa: E402


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
    g = jax.jit(jax.grad(lambda b: jnp.sum(moe.route_topk(
        x, router, K, score="sigmoid", bias=b)[1])))(bias)
    assert float(jnp.max(jnp.abs(g))) == 0.0


def test_bias_update_is_a_signed_step_toward_the_mean_load():
    counts = jnp.asarray([[0, 4, 8, 4], [5, 5, 5, 5]], jnp.int32)
    bias = jnp.asarray([[0.0, 0.5, 0.0, -0.5], [0.1, 0.2, 0.3, 0.4]])
    got = moe.router_bias_update(bias, counts, 0.001)
    np.testing.assert_allclose(got, [[0.001, 0.5, -0.001, -0.5], [0.1, 0.2, 0.3, 0.4]], atol=1e-7)
    np.testing.assert_allclose(got, A.bias_update(bias, counts.astype(jnp.float32),
                                                  {"load_balance_coeff": 0.001}), atol=1e-7)


def _sorted_plan(top_e, counts, experts_held, block_rows, tile):
    """``dropless_plan`` as it stood while it sorted: a stable ``argsort`` of
    all T k assignments by held expert. The oracle of the counted plan."""
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
    got_g = jax.jit(jax.grad(lambda w: jnp.sum(r * moe.route_topk(
        x, w, k, score=score, bias=bias, normalize=False, impl=impl)[1])))(router)
    want_g = jax.jit(jax.grad(lambda w: jnp.sum(
        r * _sorted_route(x, w, k, score, bias)[1])))(router)
    np.testing.assert_allclose(got_g, want_g, atol=1e-5 * float(jnp.max(jnp.abs(want_g))))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("score", ["softmax", "sigmoid under a bias"])
def test_layer_and_gradients_are_those_of_the_sorting_layer(score, impl, monkeypatch):
    """The layer on the rounds and the counted plan against the layer as it
    sorted (``jax.lax.top_k`` and the sorted plan in their place): the output,
    ``x``'s gradient, the router's and every expert leaf's."""
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
