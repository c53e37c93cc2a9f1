"""The row movements of the dropless expert layer as kernels
(``ops/pallas/expert_rows.py``: ``moe_rows_gather`` / ``moe_rows_combine``,
interpreted) against XLA's gathers: the layer's output and every gradient at
the cells' routings, bf16 rows, the widths the row DMA takes, and the plan's
lists that the kernels read."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.pallas import expert_rows as rk
from apex_tpu.ops.pallas import grouped_matmul as gk
from apex_tpu.transformer import moe
from comparisons import close
from moe_toy import F, H


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
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    close(f32(got[0]), f32(want[0]), 2e-2, "y")
    close(f32(got[3]), f32(want[3]), 2e-2, "dx")
    for name in want[2]:
        close(f32(got[2][name]), f32(want[2][name]), 2e-2, name)


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
    close(f32(got[0]), f32(want[0]), 2e-2, "y")
    close(f32(got[3]), f32(want[3]), 2e-2, "dx")
    for name in want[2]:
        close(f32(got[2][name]), f32(want[2][name]), 2e-2, name)

