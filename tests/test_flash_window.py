"""Sliding-window flash attention (seq-major layout): the banded kernels in
interpret mode against the XLA composition with an explicit mask, forward
and backward, at windows smaller than, equal to and larger than a tile and
than the sequence; the band's block arithmetic by hand; and the unbanded
call left as it was."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.pallas import attention as fa

pytestmark = pytest.mark.pallas


def _qkv(key, b, sq, sk, h, h_kv, d, dtype=jnp.float32):
    kq, kk, kv, kd = jax.random.split(key, 4)
    return (jax.random.normal(kq, (b, sq, h, d), dtype),
            jax.random.normal(kk, (b, sk, h_kv, d), dtype),
            jax.random.normal(kv, (b, sk, h_kv, d), dtype),
            jax.random.normal(kd, (b, sq, h, d), dtype))


def _dense(q, k, v, window):
    """Plain softmax attention under the mask ``i - window < j <= i``."""
    b, sq, h, d = q.shape
    sk, group = k.shape[1], h // k.shape[2]
    k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / d ** 0.5
    i = jnp.arange(sq)[:, None] + (sk - sq)
    j = jnp.arange(sk)[None, :]
    keep = (j <= i) & (j > i - window)
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# blocks of 128 at a sequence of 512: windows under a tile, of a tile, across
# tiles (aligned and not), of the sequence and beyond it
@pytest.mark.parametrize("backward", ["one_pass", "split"])
@pytest.mark.parametrize("window", [1, 5, 128, 129, 200, 256, 384, 512, 4096])
def test_banded_kernels_match_the_masked_oracle(window, backward, monkeypatch):
    """``split``: with no VMEM to ask for, the rule that picks the backward
    keeps the dq/dkv pair (what a longer key sequence or a bias rides)."""
    if backward == "split":
        monkeypatch.setattr(fa, "_VMEM_CAP", 0)
    q, k, v, do = _qkv(jax.random.PRNGKey(window), 2, 512, 512, 4, 2, 128)

    def run(impl, bq=128, bk=128):
        if impl == "xla":
            f = lambda q, k, v: flash_attention(  # noqa: E731
                q, k, v, causal=True, layout="bshd", impl="xla", window=window)

            def both(q, k, v):
                o, pull = jax.vjp(f, q, k, v)
                return (o, *pull(do))
            return jax.jit(both)(q, k, v)
        o, lse = fa.flash_fwd_bshd(q, k, v, scale=128 ** -0.5, causal=True, bq=bq, bk=bk,
                                   interpret=True, window=window)
        return (o, *fa.flash_bwd_bshd(q, k, v, o, lse, do, scale=128 ** -0.5, causal=True,
                                      bq=bq, bk=bk, interpret=True, window=window))

    names = str(jax.make_jaxpr(lambda: run("pallas"))())
    assert ("flash_bwd_bshd_win_fused" in names) == (backward == "one_pass")
    assert ("flash_bwd_bshd_win_dkv" in names) == (backward == "split")
    want = run("xla")
    np.testing.assert_allclose(want[0], _dense(q, k, v, window), atol=2e-5)
    for blocks in ((128, 128), (256, 128), (128, 256)):
        for got, ref, name in zip(run("pallas", *blocks), want, ("o", "dq", "dk", "dv")):
            np.testing.assert_allclose(got, ref, atol=5e-5, err_msg=f"{name} at blocks {blocks}")


def test_public_entry_differentiates_through_the_banded_kernels():
    """``flash_attention(window=)`` under ``jax.grad`` in bf16, a longer key
    sequence than query sequence (positions bottom-right aligned)."""
    q, k, v, do = _qkv(jax.random.PRNGKey(0), 1, 256, 512, 2, 1, 128, jnp.bfloat16)

    def loss(impl):
        return lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, layout="bshd", impl=impl, window=200).astype(jnp.float32)
            * do.astype(jnp.float32))

    got = jax.jit(jax.grad(loss("pallas"), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss("xla"), argnums=(0, 1, 2)))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g.astype(jnp.float32), w.astype(jnp.float32),
                                   atol=0.06, rtol=0.05)
    o = flash_attention(q, k, v, causal=True, layout="bshd", impl="pallas", window=200)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    np.testing.assert_allclose(f32(o), _dense(f32(q), f32(k), f32(v), 200), atol=0.03)


def test_band_walks_only_the_blocks_it_touches():
    """At the cell's shape (8,192 positions, blocks of 1,024, window 2,048) a
    q block walks 3 kv blocks of 8 and a kv block 3 q blocks; the index maps
    hold the band's last block on a step past it."""
    steps = lambda *a: fa._band_walk(True, *a)[0]  # noqa: E731
    assert steps(8, 1024, 1024, 8, 0, 2047, 0) == 3
    assert steps(8, 1024, 1024, 8, 0, 0, 2047) == 3
    assert steps(16, 512, 512, 16, 0, 2047, 0) == 5
    # a window of one block and two keys reaches a third block
    assert steps(8, 1024, 1024, 8, 0, 1025, 0) == 3
    assert steps(8, 1024, 1024, 8, 0, 1024, 0) == 2
    assert fa._band_walk(False, 8, 1024, 1024, 8, 0, 0, 0)[0] == 8
    held = fa._band_walk(True, 8, 1024, 1024, 8, 0, 2047, 0)[1]
    assert [int(held(5, s)) for s in range(4)] == [3, 4, 5, 5]   # a step past the band
    first = [int(fa._band_first(i, 1024, 1024, 0, 2047)) for i in range(8)]
    last = [int(fa._band_last(i, 1024, 1024, 0, 0, 8)) for i in range(8)]
    assert first == [0, 0, 0, 1, 2, 3, 4, 5] and last == list(range(8))
    q_first = [int(fa._band_first(j, 1024, 1024, 0, 0)) for j in range(8)]
    q_last = [int(fa._band_last(j, 1024, 1024, 0, 2047, 8)) for j in range(8)]
    assert q_first == list(range(8)) and q_last == [2, 3, 4, 5, 6, 7, 7, 7]


def test_the_grid_is_the_bands_and_the_names_say_so():
    q, k, v, do = _qkv(jax.random.PRNGKey(1), 1, 1024, 1024, 2, 1, 128)
    f = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, layout="bshd", impl="pallas", window=256)
    text = str(jax.make_jaxpr(lambda *a: jax.vjp(f, *a)[1](do))(q, k, v))
    for name in ("flash_fwd_bshd_win", "flash_bwd_bshd_win_fused"):
        assert name in text
    # the one-pass backward walks the same band: 8 q blocks, 3 kv steps each
    bwd = str(jax.make_jaxpr(lambda q, k, v, o, lse: fa.flash_bwd_bshd(
        q, k, v, o, lse, do, scale=1.0, causal=True, bq=128, bk=128, interpret=True,
        window=256))(q, k, v, q, jnp.zeros((1, 2, 1024), jnp.float32)))
    assert "grid=(1, 2, 8, 3)" in bwd
    fwd = str(jax.make_jaxpr(lambda q, k, v: fa.flash_fwd_bshd(
        q, k, v, scale=1.0, causal=True, bq=128, bk=128, interpret=True, window=256))(q, k, v))
    assert "grid=(2, 8, 3)" in fwd.replace("Grid", "grid") or "(2, 8, 3)" in fwd


def test_no_window_is_the_call_it_was():
    """``window=None`` builds the kernels from the arguments they always
    had: the same jaxpr as a call that never names the argument, under the
    unbanded names."""
    q, k, v, do = _qkv(jax.random.PRNGKey(2), 1, 256, 256, 2, 1, 128)

    def text(**kw):
        f = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, causal=True, layout="bshd", impl="pallas", **kw)
        return str(jax.make_jaxpr(lambda *a: jax.vjp(f, *a)[1](do))(q, k, v))

    assert text(window=None) == text()
    assert "_win" not in text()
    assert "flash_fwd_bshd" in text() and "flash_bwd_bshd_fused" in text()


@pytest.mark.parametrize("kw", [dict(causal=False), dict(layout="bhsd"), dict(window=0),
                                dict(bias=jnp.zeros((1, 128, 128)))])
def test_window_refuses_what_it_cannot_mask(kw):
    q = jnp.zeros((1, 128, 1, 128))
    args = dict(causal=True, layout="bshd", window=64)
    args.update(kw)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, **args)


# --- what a forward grid step is, counted from the mask ------------------------

def _mask(sq, sk, causal, window):
    rows = np.arange(sq)[:, None] + (sk - sq)
    cols = np.arange(sk)[None, :]
    keep = np.ones((sq, sk), bool)
    if causal:
        keep &= cols <= rows
    if window is not None:
        keep &= cols > rows - window
    return keep


@pytest.mark.parametrize("sq,sk,bq,bk,causal,window", [
    (8192, 8192, 1024, 1024, True, None),       # the 8k cells' full layers
    (8192, 8192, 1024, 1024, True, 2048),       # trinity-train-8k's banded layers
    (1024, 1024, 512, 512, True, None),         # the pair backward's blocks
    (1024, 1024, 1024, 1024, True, None),       # the pair forward's one tile
    (8192, 8192, 1024, 1024, False, None),
    (512, 1024, 128, 128, True, None),          # more keys than queries
    (512, 1024, 128, 256, True, None),
    (1024, 1024, 256, 128, True, None),
    (384, 512, 128, 128, True, 300),
    (512, 512, 128, 128, True, 1),
    (512, 512, 128, 128, True, 129),
    (512, 512, 128, 256, True, 256),
    (512, 512, 128, 128, True, 4096),
    (256, 1024, 128, 128, False, None),
])
def test_forward_tiles_counts_the_mask(sq, sk, bq, bk, causal, window):
    """``forward_tiles`` against the mask itself: a tile runs iff it holds a
    visible score, is fully visible iff it holds no hidden one, and the grid
    is as long as the widest run of running tiles a q block has."""
    keep = _mask(sq, sk, causal, window)
    tiles = keep.reshape(sq // bq, bq, sk // bk, bk).transpose(0, 2, 1, 3)
    runs, full = tiles.any((2, 3)), tiles.all((2, 3))
    steps = runs.sum(1).max()
    want = (int(runs.sum()), int(full.sum()), int(runs.shape[0] * steps - runs.sum()))
    assert fa.forward_tiles(sq, sk, bq, bk, causal, window) == want
    # the kernel's own predicates, tile by tile
    for i in range(sq // bq):
        for j in range(sk // bk):
            run, inner = fa._tile_kind(i, j, bq, bk, sk - sq, causal, window)
            # a band's tile past the diagonal never reaches the kernel as a
            # tile under its lower edge: `run` is the diagonal's test alone
            if window is None or runs[i, j] or j * bk > (i + 1) * bq - 1 + sk - sq:
                assert bool(run) == bool(runs[i, j]), (i, j)
            if runs[i, j]:
                assert bool(inner) == bool(full[i, j]), (i, j)


def test_forward_tiles_at_the_cells_shapes():
    assert fa.forward_tiles(8192, 8192, 1024, 1024, True) == (36, 28, 28)
    assert fa.forward_tiles(8192, 8192, 1024, 1024, True, 2048) == (21, 7, 3)
    assert fa.forward_tiles(1024, 1024, 512, 512, True) == (3, 1, 1)
    assert fa.forward_tiles(1024, 1024, 1024, 1024, True) == (1, 0, 0)
    assert fa.forward_tiles(8192, 8192, 1024, 1024, False) == (64, 64, 0)
    # a length is a run-time operand: the kernel sends a tile it ends in
    # through the mask, and skips one past it
    kind = lambda i, j, causal, kvlen: fa._tile_in_length(  # noqa: E731
        *fa._tile_kind(i, j, 128, 128, 0, causal, None), j, 128, kvlen)
    assert kind(3, 1, True, 200) == (True, False)
    assert kind(3, 0, True, 200) == (True, True)
    assert kind(3, 2, True, 200) == (False, False)
    assert kind(0, 1, False, 256) == (True, True)


@pytest.mark.parametrize("sq,sk,bq,bk", [
    (8192, 8192, 1024, 1024), (1024, 1024, 512, 512), (512, 1024, 128, 128),
    (512, 1024, 128, 256), (1024, 1024, 256, 128), (1024, 1024, 128, 256),
    (1024, 512, 128, 128),                      # more queries than keys: rows that see nothing
])
def test_the_causal_walk_holds_the_last_needed_block(sq, sk, bq, bk):
    """The one kv index rule of the forwards and the one-pass backward, over
    every (i, j): the step itself where the tile runs, held at the q block's
    last needed block where it is skipped, never past it and never below 0."""
    nq, nk, off = sq // bq, sk // bk, sk - sq
    steps, block = fa._kv_walk(True, None, nq, bq, bk, nk, off)
    assert steps == nk
    keep = _mask(sq, sk, True, None)
    runs = keep.reshape(nq, bq, nk, bk).any((1, 3))
    for i in range(nq):
        needed = max(int(runs[i].sum()) - 1, 0)          # the running tiles are 0 .. needed
        held = [int(block(jnp.int32(i), jnp.int32(j))) for j in range(nk)]
        assert held == [int(block(i, j)) for j in range(nk)]      # traced and static agree
        for j in range(nk):
            assert held[j] == (j if runs[i, j] else needed), (i, j)
            assert 0 <= held[j] <= needed
    # not causal: every block in order; banded: the band's own walk
    assert [fa._kv_walk(False, None, nq, bq, bk, nk, off)[1](0, j) for j in range(nk)] == list(
        range(nk))
    if off >= 0:
        assert fa._kv_walk(True, 2 * bk, nq, bq, bk, nk, off)[0] == fa._band_walk(
            True, nq, bq, bk, nk, off, 2 * bk - 1, 0)[0]
