"""The row movements of the dropless expert layer as kernels
(``ops/pallas/expert_rows.py``: ``moe_rows_gather`` / ``moe_rows_combine``,
interpreted) against XLA's gathers: the layer's output and every gradient at
the cells' routings, bf16 rows, the widths the kernels take — each at its own,
the group's padding inside the groups — and the plan's lists that the kernels
read."""
import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.pallas import expert_rows as rk
from apex_tpu.ops.pallas import grouped_matmul as gk
from apex_tpu.transformer import moe
from comparisons import close
from moe_toy import F, H


def _f32(a):
    return np.asarray(a, np.float32)


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
    (_, (y, aux)), (gp, gx) = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True))(p, x)
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
    close(got[0], want[0], 2e-5, "y")
    close(got[3], want[3], 2e-5, "dx")
    for name in want[2]:
        close(got[2][name], want[2][name], 2e-5, name)


def _one_block_move(tokens, top_k, held, width, seed=40):
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
    close(_f32(got[0]), _f32(want[0]), 2e-2, "y")
    close(_f32(got[3]), _f32(want[3]), 2e-2, "dx")
    for name in want[2]:
        close(_f32(got[2][name]), _f32(want[2][name]), 2e-2, name)


@pytest.mark.parametrize("width, dtype, takes, lines", [
    (2048, jnp.bfloat16, "pallas", 8), (1024, jnp.float32, "pallas", 8),
    # ling3- and nemotron3-train-8k's rows: 10 and 10.5 lines of 128 words, in groups of 10 and 11
    (2560, jnp.bfloat16, "pallas", 10), (2688, jnp.bfloat16, "pallas", 11),
    (2688, jnp.float32, "pallas", 21),
    # a width the parent moved at 2,048: six lines
    (1536, jnp.bfloat16, "pallas", 6),
    # four lines, half a tile of eight
    (1024, jnp.bfloat16, "xla", 4), (512, jnp.float32, "xla", 4)])
def test_compiled_movements_take_every_width_over_half_a_tile_of_lines_at_its_own(
        width, dtype, takes, lines, monkeypatch):
    """Compiled as interpreted, a row's group is the lines that hold it and
    the arrays around the kernels keep the row's own width: a width of whole
    lane tiles rides the kernels where its group is more than half a tile of
    eight lines — the widths that rode them widened before; narrower ones keep
    XLA's movements under ``impl="pallas"`` too (the grouped products take
    them) instead of raising."""
    monkeypatch.setattr(moe._backend, "interpret_mode", lambda: False)
    a = jax.ShapeDtypeStruct((256, width), dtype)
    assert rk.groups_of(width, dtype) == lines
    assert moe._rows_impl("pallas", a) == takes
    assert moe._rows_impl("xla", a) == "xla"
    assert not hasattr(moe, "_rows_width") and not hasattr(moe, "_widened")


# a row that ends inside its group's last line (384: 1.5 lines of packed words in 2, as
# nemotron3-train-8k's 2,688 is 10.5 in 11) and ling3-train-8k's 10 whole lines, which are no
# whole tiles: the groups the chip runs, walked here by the interpreter
OWN_WIDTHS = [(384, 2), (2560, 10), (2688, 11)]


@pytest.mark.parametrize("width, lines", OWN_WIDTHS)
def test_rows_of_no_whole_tiles_move_at_their_own_width(width, lines):
    """All four movements on the kernels against XLA's, operands and results
    (rows, width) as they are: rows <- tokens and its cotangent ``x`` (the
    unweighted sum) bit for bit, tokens <- rows within bf16's rounding of the
    float32 sum, its cotangent ``y`` (one rounding of the same float32
    product) bit for bit, the weights' (``dots``, float32 sums in another
    order) to 1e-5 of the largest."""
    move, top_p, rows = _one_block_move(192, 4, 8, 16)
    k = jax.random.split(jax.random.PRNGKey(60), 3)
    x, g = (jax.random.normal(k[i], (192, width)).astype(jnp.bfloat16) for i in (0, 1))
    y = jax.random.normal(k[2], (rows, width)).astype(jnp.bfloat16)
    assert rk.groups_of(width, x.dtype) == lines and moe._rows_impl("pallas", x) == "pallas"

    @functools.partial(jax.jit, static_argnums=4)
    def movements(x, y, w, g, impl):
        xs, pull = jax.vjp(lambda x: moe._rows_from_tokens(x, move, impl), x)
        out, pull_out = jax.vjp(lambda y, w: moe._tokens_from_rows(y, w, move, impl), y, w)
        return (xs, pull(y)[0], out, *pull_out(g))
    want = movements(x, y, top_p, g, "xla")
    got = movements(x, y, top_p, g, "pallas")
    for a, b, name in zip(got, want, ("rows", "dx", "tokens", "dy", "dweights")):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        if name == "tokens":
            close(_f32(a), _f32(b), 2e-2, name)
        elif name == "dweights":
            close(a, b, 1e-5, name)
        else:
            np.testing.assert_array_equal(_f32(a), _f32(b), err_msg=name)
    assert float(jnp.max(jnp.abs(got[4]))) > 0


@pytest.mark.parametrize("hidden", [384, 2688])
def test_layer_on_rows_that_end_inside_their_group_matches_the_xla_composition(
        hidden, top_k=6, held=(0, 8)):
    """The whole layer at such a width, forward and every gradient: the
    movements run on the kernels at (rows, hidden) and nothing of a group's
    padding reaches a result."""
    p, x = _movement_operands(256, hidden, held, 64, jnp.bfloat16)
    assert moe._rows_impl("pallas", x) == "pallas"
    want = _layer_and_grads("xla", p, x, top_k, held)
    got = _layer_and_grads("pallas", p, x, top_k, held)
    np.testing.assert_array_equal(got[1]["expert_load"], want[1]["expert_load"])
    assert got[0].shape == want[0].shape == (256, hidden) and got[3].shape == (256, hidden)
    close(_f32(got[0]), _f32(want[0]), 2e-2, "y")
    close(_f32(got[3]), _f32(want[3]), 2e-2, "dx")
    for name in want[2]:
        close(_f32(got[2][name]), _f32(want[2][name]), 2e-2, name)


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def test_no_pad_and_no_slice_stands_around_a_movement_at_2688(top_k=6, held=(0, 8)):
    """The traced layer and its gradients at ``nemotron3-train-8k``'s width:
    every ``moe_rows_*`` call takes and gives the row's own width (its groups
    apart), no ``pad`` makes anything wider than the row, and no ``slice``
    cuts anything wider down."""
    hidden = 2688
    p, x = _movement_operands(256, hidden, held, 64, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda p, x: _layer_and_grads("pallas", p, x, top_k, held)[2:])(p, x)
    called = collections.Counter()
    for eqn in _equations(jaxpr.jaxpr):
        shapes = lambda vs: [v.aval.shape for v in vs if getattr(v.aval, "ndim", 0) == 2]  # noqa: E731
        name = eqn.primitive.name
        if name == "pallas_call" and eqn.params["name"].startswith("moe_rows"):
            called[eqn.params["name"]] += 1
            assert {s[1] for s in shapes(eqn.invars + eqn.outvars)} <= {hidden, rk.LANES, top_k}
        elif name == "pad":
            assert all(s[1] <= hidden for s in shapes(eqn.outvars)), eqn
        elif name in ("slice", "dynamic_slice"):
            assert not [s for s in shapes(eqn.invars[:1]) if s[1] > hidden], eqn
    assert set(called) == {"moe_rows_gather", "moe_rows_gather_dots", "moe_rows_pack",
                           "moe_rows_combine", "moe_rows_combine_weighted"}


@pytest.mark.parametrize("width, lines", [(1152, 5), (2688, 11)])
def test_garbage_past_the_row_and_in_unused_tiles_never_reaches_a_result(
        width, lines, dtype=jnp.bfloat16):
    """Groups whose last line's high half holds NaN from column ``width`` on
    and, for the combine, NaN in every group of the row tiles not in use
    (``moe_rows_pack`` leaves those unwritten): both kernels give what they
    give on zeros there."""
    assert rk.groups_of(width, dtype) == lines
    wide = 2 * lines * rk.LANES
    move, top_p, rows = _one_block_move(192, 4, 8, 16)
    x = jax.random.normal(jax.random.PRNGKey(70), (192, width)).astype(dtype)
    y = jax.random.normal(jax.random.PRNGKey(71), (rows, width)).astype(dtype)

    def spoiled(a):
        """The groups of the row continued with NaN to the last line's end:
        the same lines, the same pairing ``c`` / ``c + 128 lines``."""
        low, high = a[:, :wide // 2], a[:, wide // 2:]
        high = jnp.pad(high, ((0, 0), (0, wide - width)), constant_values=jnp.nan)
        return rk._words(low, high).reshape(-1, rk.LANES)
    clean = rk.as_groups(x)
    assert clean.shape == spoiled(x).shape and not np.array_equal(clean, spoiled(x))
    scale = jnp.where(move["row_valid"], 1.5, 0.0)
    gather = lambda groups: rk.moe_rows_gather(  # noqa: E731
        groups, move["row_token"], scale, move["n_used"], y, width=width, dtype=dtype,
        interpret=True)
    for got, want in zip(gather(spoiled(x)), gather(clean)):
        np.testing.assert_array_equal(_f32(got), _f32(want))
    assert np.isfinite(_f32(gather(spoiled(x))[0])).all()

    in_use = jnp.repeat(jnp.arange(rows // gk.TM) < move["n_used"][0], gk.TM * lines)[:, None]
    packed = rk.moe_rows_pack(y, move["n_used"], interpret=True)
    combine = lambda groups: rk.moe_rows_combine(  # noqa: E731
        groups, move["tile_rows"], move["tile_count"], move["rank"],
        jnp.pad(top_p, ((0, move["rank"].shape[0] - 192), (0, 0))),
        tokens=192, width=width, dtype=dtype, interpret=True)
    want = combine(jnp.where(in_use, packed, 0))
    got = combine(jnp.where(in_use, spoiled(y), jnp.uint32(0x7FC07FC0)))
    np.testing.assert_array_equal(_f32(got), _f32(want))
    assert np.isfinite(_f32(got)).all() and float(jnp.max(jnp.abs(got))) > 0
    close(_f32(got), _f32(moe._tokens_from_rows(y, top_p, move, "xla")), 2e-2)
