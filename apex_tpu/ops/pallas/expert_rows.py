"""Pallas row movements of a dropless expert layer: tokens to expert-sorted
rows and back, by one DMA a row in use and none for the rest.

A row of a (N, H) array in its tiled layout is no DMA's unit (eight or
sixteen rows interleave in one tile), so the SOURCE of either movement
arrives as its GROUPS: (N * G, 128) words of 32 bits, a row in G consecutive
lines of 128, contiguous in HBM. float32 rows are their own words; a bf16 row
packs column ``c`` and column ``c + 128 G`` into one word. G (``groups_of``)
is the lines that hold the row and no more, whole tiles of eight or not (a
DMA may start at any line of such a buffer and a strided load take any
stride): 2,048 bf16 columns are 8 lines, 2,560 are 10, and a row is as many
bytes as it was; 2,688 are 10.5 and ride in 11, the last line's high half
zero. That padding lives in the groups and nowhere else: every tiled operand
and result is (rows, H) at the row's own width, and a kernel's static loop
over a group's column blocks leaves out the blocks at or past H. A kernel
fetches a row's group with one DMA into VMEM and reads the staged groups back
column block by column block with a strided load, which is the relayout to
(rows, H). The RESULTS leave in the plain tiled layout. ``as_groups`` builds
the groups of every row in XLA (the tokens: all of them are read);
``moe_rows_pack`` those of the row tiles in use and no others.

* ``moe_rows_gather``   out[r] = scale[r] * x[row_src[r]] over tiles of
  ``TM`` rows (a scale of 0: a row of zeros, whatever was fetched; tiles at or
  past ``n_used``: zeros and no DMA); with ``dot_with`` also
  ``dots[r] = sum_h x[row_src[r]] dot_with[r]`` (0 where the scale is).
* ``moe_rows_combine``  out[t] = sum over the token's listed rows of
  ``weight * y[row]`` over tiles of ``TT`` tokens: each tile walks ITS
  compact list of rows (``tile_rows[i, :tile_count[i]]``, in token order),
  ``LC`` at a time, and sums them on the MXU against the (TT, LC) matrix
  that holds each staged row's weight in its token's line — float32
  accumulation; for bf16 rows the float32 weights go as three bf16 parts,
  so every product is exact.

The two are each other's transpose: the gather serves ``tokens -> rows`` and
the cotangent of ``rows -> tokens``, the combine the other two.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.pallas.grouped_matmul import TM

LANES = 128
TILE = 8               # lines of a tile of 32-bit words
TT = 128               # tokens a tile of the combine
LC = 128               # rows the combine stages and sums at a time
SUB = 16               # row DMAs started a loop step and waited for at once
_VMEM = 64 * 2 ** 20
_HIGH = 0xFFFF0000      # the upper bf16 of a packed word


def _packed(dtype):
    return jnp.dtype(dtype) == jnp.bfloat16


def groups_of(width, dtype):
    """Lines of 128 words that one row's group takes: those that hold it."""
    return -(-width // (2 * LANES if _packed(dtype) else LANES))


def shapes_ok(width, dtype, interpret):
    """Widths the kernels take, each at its own: whole lane tiles and,
    compiled, a group of more than half a tile's eight lines (1,024 bf16
    columns are four: XLA's movements, as such rows ran before the row
    kernels and since)."""
    return width % LANES == 0 and (interpret or 2 * groups_of(width, dtype) > TILE)


def _bits(part):
    return jax.lax.bitcast_convert_type(part.astype(jnp.float32), jnp.uint32)


def _low(part):
    """bf16 (as float32 bits) in the lower half of a word each."""
    return _bits(part) >> 16


def _words(low, high):
    """bf16 ``low`` and ``high`` in one word each pair."""
    return _low(low) | (_bits(high) & jnp.uint32(_HIGH))


def as_groups(a):
    """(N, H) -> (N * G, 128) words: every row contiguous in its G lines —
    the layout a row DMA can address. Where a bf16 row ends inside its last
    line, the high half's zeros from column H on are written here, in the one
    pass that makes the words (a ``pad`` that cuts the low half off and adds
    them: one fusion with the shifts, nothing padded before it)."""
    if not _packed(a.dtype):
        return a.astype(jnp.float32).reshape(-1, LANES)
    half = groups_of(a.shape[1], a.dtype) * LANES
    low, past = a[:, :half], 2 * half - a.shape[1]
    high = a[:, half:] if not past else jax.lax.pad(
        a, jnp.zeros((), a.dtype), ((0, 0, 0), (-half, past, 0)))
    return _words(low, high).reshape(-1, LANES)


def _parts(staged, s, rows, groups, width):
    """Line ``s`` of each of ``rows`` staged groups as float32 column blocks
    ``[(first column, (rows, 128))]``: one for float32 words, two for packed
    — the low block and, ``128 groups`` columns on, the high one, left out
    where the row ends before it."""
    words = staged[pl.ds(s, rows, stride=groups), :]
    if words.dtype == jnp.float32:
        return [(s * LANES, words)]
    value = lambda w: jax.lax.bitcast_convert_type(w, jnp.float32)  # noqa: E731
    low, high = (s * LANES, value(words << 16)), (groups + s) * LANES
    return [low] if high >= width else [low, (high, value(words & jnp.uint32(_HIGH)))]


def _last_used(i, n_used):
    """Block index of tile ``i``, or of the last tile in use past ``n_used``:
    an unused tile keeps that block, so nothing is copied for it either way."""
    return jnp.minimum(i, jnp.maximum(n_used[0] - 1, 0)), 0


def _pack_kernel(n_used, a_ref, o_ref, *, groups):
    @pl.when(pl.program_id(0) < n_used[0])
    def _():
        cols = lambda first: a_ref[:, first:first + LANES]  # noqa: E731
        for s in range(groups):
            low, high = s * LANES, (groups + s) * LANES
            if o_ref.dtype == jnp.float32:
                words = cols(low).astype(jnp.float32)
            elif high < a_ref.shape[1]:
                words = _words(cols(low), cols(high))
            else:                           # the row ends before this line's high half
                words = _low(cols(low))
            o_ref[pl.ds(s, TM, stride=groups), :] = words


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_rows_pack(a, n_used, *, interpret=False):
    """``as_groups`` of the rows (R, H) that tiles of ``TM`` rows below
    ``n_used`` (1,) hold; the other tiles are neither read nor written (they
    keep what the buffer held: nothing may fetch them)."""
    R, width = a.shape
    groups = groups_of(width, a.dtype)
    return pl.pallas_call(
        functools.partial(_pack_kernel, groups=groups), name="moe_rows_pack",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(R // TM,),
            in_specs=[pl.BlockSpec((TM, width), _last_used)],
            out_specs=pl.BlockSpec((TM * groups, LANES), _last_used)),
        out_shape=jax.ShapeDtypeStruct(
            (R * groups, LANES), jnp.uint32 if _packed(a.dtype) else jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM),
        interpret=interpret,
    )(n_used, a)


def list_length(per_token):
    """Entries a token tile's list holds where a token has at most
    ``per_token`` rows: whole 1,024-word tiles, the unit of a 1-D int32 DMA."""
    return -(-TT * per_token // 1024) * 1024


def _start_rows(index_of, first, src_hbm, buf, sem, groups):
    """Start the DMA of ``SUB`` rows' groups: row ``first + u`` of ``buf``
    ((rows * groups, 128)) from the source row ``index_of(first + u)``."""
    for u in range(SUB):
        j = first + u
        pltpu.make_async_copy(
            src_hbm.at[pl.ds(pl.multiple_of(index_of(j) * groups, groups), groups)],
            buf.at[pl.ds(pl.multiple_of(j * groups, groups), groups)], sem).start()


def _wait_rows(first, rows, src_hbm, buf, sem, groups):
    """Wait until ``rows`` started rows have landed from ``first`` on: one
    wait for all their bytes."""
    pltpu.make_async_copy(
        src_hbm.at[pl.ds(0, rows * groups)],
        buf.at[pl.ds(pl.multiple_of(first * groups, groups), rows * groups)], sem).wait()


def _gather_kernel(row_src, n_used, x_hbm, scale_ref, *rest, groups, with_dots):
    if with_dots:
        y_ref, o_ref, dots_ref, buf, sem = rest
    else:
        o_ref, buf, sem = rest
    i = pl.program_id(0)
    used = n_used[0]

    def start(tile):
        slot = tile % 2
        # every row of a tile in use is fetched, the ones scaled by 0 too: one wait a tile
        jax.lax.fori_loop(0, TM // SUB, lambda g, c: _start_rows(
            lambda j: row_src[tile * TM + j], g * SUB, x_hbm, buf.at[slot],
            sem.at[slot], groups) or c, 0)

    @pl.when(jnp.logical_and(i == 0, used > 0))
    def _():
        start(0)

    @pl.when(i + 1 < used)
    def _():
        start(i + 1)

    @pl.when(i < used)
    def _():
        _wait_rows(0, TM, x_hbm, buf.at[i % 2], sem.at[i % 2], groups)
        staged = buf.at[i % 2]
        # the tile's scales lie along lanes; as a column they scale rows
        eye = (jax.lax.broadcasted_iota(jnp.int32, (TM, TM), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (TM, TM), 1))
        scale = jnp.sum(jnp.where(eye, scale_ref[...], 0.0), axis=1, keepdims=True)
        keep = scale != 0.0
        dots = jnp.zeros((TM, 1), jnp.float32)
        for s in range(groups):
            for first, part in _parts(staged, s, TM, groups, o_ref.shape[1]):
                cols = slice(first, first + LANES)
                o_ref[:, cols] = jnp.where(keep, part * scale, 0.0).astype(o_ref.dtype)
                if with_dots:
                    dots = dots + jnp.sum(
                        jnp.where(keep, part, 0.0) * y_ref[:, cols].astype(jnp.float32),
                        axis=1, keepdims=True)
        if with_dots:
            dots_ref[...] = jnp.sum(jnp.where(eye, dots, 0.0), axis=0, keepdims=True)

    @pl.when(i >= used)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        if with_dots:
            dots_ref[...] = jnp.zeros_like(dots_ref)


@functools.partial(jax.jit, static_argnames=("width", "dtype", "interpret"))
def moe_rows_gather(x_groups, row_src, scale, n_used, dot_with=None, *, width, dtype,
                    interpret=False):
    """x_groups ``as_groups`` of (T, width); row_src (R,) int32 in [0, T),
    scale (R,) float32, n_used (1,) int32 tiles of ``TM`` rows in use;
    dot_with (R, width) or None -> (R, width) in ``dtype`` [, (R,) float32
    dots]."""
    R = row_src.shape[0]
    tiles, groups = R // TM, groups_of(width, dtype)
    with_dots = dot_with is not None
    lanes = pl.BlockSpec((None, 1, TM), lambda i, rs, nu: (i, 0, 0))
    rows = pl.BlockSpec((TM, width), lambda i, rs, nu: (i, 0))
    held = pl.BlockSpec((TM, width), lambda i, rs, nu: _last_used(i, nu))
    out = pl.pallas_call(
        functools.partial(_gather_kernel, groups=groups, with_dots=with_dots),
        name="moe_rows_gather_dots" if with_dots else "moe_rows_gather",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), lanes] + [held] * with_dots,
            out_specs=[rows, lanes] if with_dots else rows,
            scratch_shapes=[pltpu.VMEM((2, TM * groups, LANES), x_groups.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=([jax.ShapeDtypeStruct((R, width), dtype),
                    jax.ShapeDtypeStruct((tiles, 1, TM), jnp.float32)] if with_dots
                   else jax.ShapeDtypeStruct((R, width), dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM),
        interpret=interpret,
    )(row_src, n_used, x_groups, scale.reshape(tiles, 1, TM), *([dot_with] * with_dots))
    return (out[0], out[1].reshape(R)) if with_dots else out


def _combine_kernel(tile_count, y_hbm, list_hbm, rank_ref, *rest, groups, top_k, weighted, float32_rows):
    if weighted:
        w_ref, o_ref, rows, buf, acc, sem, list_sem = rest
    else:
        o_ref, rows, buf, acc, sem, list_sem = rest
    i = pl.program_id(0)
    count = tile_count[i]
    chunks = (count + LC - 1) // LC

    @pl.when(count > 0)
    def _():
        length = rows.shape[0]
        copy = pltpu.make_async_copy(
            list_hbm.at[pl.ds(pl.multiple_of(i * length, length), length)], rows, list_sem)
        copy.start()
        copy.wait()

    def dmas(c, wait):
        """Chunk ``c`` of the list, ``SUB`` rows at a time as far as the list
        goes (past its end within the last ``SUB``: row 0, which weighs nothing)."""
        slot = c % 2

        def some(g, carry):
            @pl.when(g * SUB < count - c * LC)
            def _():
                if wait:
                    _wait_rows(g * SUB, SUB, y_hbm, buf.at[slot], sem.at[slot], groups)
                else:
                    _start_rows(lambda j: rows[c * LC + j], g * SUB, y_hbm, buf.at[slot],
                                sem.at[slot], groups)
            return carry
        jax.lax.fori_loop(0, LC // SUB, some, 0)

    @pl.when(count > 0)
    def _():
        dmas(0, False)

    acc[...] = jnp.zeros_like(acc)
    lane = jax.lax.broadcasted_iota(jnp.int32, (TT, LC), 1)
    line = jax.lax.broadcasted_iota(jnp.int32, (LC, LANES), 0)

    def chunk(c, carry):
        @pl.when(c + 1 < chunks)
        def _():
            dmas(c + 1, False)

        dmas(c, True)
        # (TT, LC): the weight of staged row j in the line of the token that owns it
        rank = rank_ref[...] - c * LC
        mix = jnp.zeros((TT, LC), jnp.float32)
        for k in range(top_k):
            mix = jnp.where(rank[:, k:k + 1] == lane, w_ref[:, k:k + 1] if weighted else 1.0, mix)
        if float32_rows:
            parts = [mix]
        else:                        # three bf16 parts carry all 24 bits of a float32 weight
            parts, left = [], mix
            for _ in range(3 if weighted else 1):
                parts.append(left.astype(jnp.bfloat16))
                left = left - parts[-1].astype(jnp.float32)
        staged = buf.at[c % 2]
        filled = line < count - c * LC           # rows past the list hold what was there before
        for s in range(groups):
            for first, part in _parts(staged, s, LC, groups, o_ref.shape[1]):
                part = jnp.where(filled, part, 0.0)
                if float32_rows:
                    add = jax.lax.dot(parts[0], part, precision=jax.lax.Precision.HIGHEST,
                                      preferred_element_type=jnp.float32)
                else:
                    part = part.astype(jnp.bfloat16)
                    add = sum(jax.lax.dot(m, part, preferred_element_type=jnp.float32)
                              for m in parts)
                acc[:, first:first + LANES] += add
        return carry

    jax.lax.fori_loop(0, chunks, chunk, 0)
    o_ref[...] = acc[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tokens", "width", "dtype", "interpret"))
def moe_rows_combine(y_groups, tile_rows, tile_count, rank, weights=None, *, tokens, width,
                     dtype, interpret=False):
    """y_groups ``as_groups`` of (R, width); tile_rows (tiles, L) int32 the
    rows each tile of ``TT`` tokens sums, in token order, tile_count (tiles,)
    how many; rank (tiles * TT, k) int32 the place in its tile's list of each
    of a token's k assignments (negative: none); weights (tiles * TT, k)
    float32 or None (all 1) -> (tokens, width) in ``dtype``."""
    tiles, top_k = tile_rows.shape[0], rank.shape[1]
    groups = groups_of(width, dtype)
    weighted = weights is not None
    per_token = pl.BlockSpec((TT, top_k), lambda i, tc: (i, 0))
    return pl.pallas_call(
        functools.partial(_combine_kernel, groups=groups, top_k=top_k, weighted=weighted,
                          float32_rows=not _packed(dtype)),
        name="moe_rows_combine_weighted" if weighted else "moe_rows_combine",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY),
                      per_token] + [per_token] * weighted,
            out_specs=pl.BlockSpec((TT, width), lambda i, tc: (i, 0)),
            scratch_shapes=[pltpu.SMEM((tile_rows.shape[1],), jnp.int32),
                            pltpu.VMEM((2, LC * groups, LANES), y_groups.dtype),
                            pltpu.VMEM((TT, width), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((tokens, width), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM),
        interpret=interpret,
    )(tile_count, y_groups, tile_rows.reshape(-1), rank, *([weights] * weighted))
