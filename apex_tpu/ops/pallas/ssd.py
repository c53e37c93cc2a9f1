"""Pallas kernels of the chunked state-space scan of Mamba-2 (SSD): a scalar
decay a head, a float32 state a head in VMEM, and only ``x, dt, B, C`` and
``y`` (backward: their cotangents too) crossing HBM, once a pass.

Per head (``P`` features) with the ``B``, ``C`` (``N`` wide) of its group,
``a_t = dt_t A`` and float32 state ``S (N, P)``:

    S_t = e^{a_t} S_{t-1} + dt_t B_t x_t^T;        y_t = S_t^T C_t + D x_t

In a chunk of ``Q`` tokens with ``g`` the cumulative log decay inside it:

    L_ij = e^{g_i - g_j} (i >= j);   M = (C B^T) . L . dt_j;   w_j = e^{g_Q - g_j} dt_j
    y = M x + e^{g} (C S_0) + D x;   S_Q = e^{g_Q} S_0 + B^T (w x)

``C B^T`` is a group's, computed once a chunk for its heads; nothing is ever
divided by a decay. A head narrower than a lane tile rides with its
neighbours: the state of ``128 / P`` heads is one (N, 128) float32 array,
``C S_0`` and ``B^T (w x)`` are one product a lane tile with the heads'
factors chosen lane by lane, and ``M x`` takes each head's own ``M`` against
the tile with the other heads' lanes zeroed (on a 128-wide MXU a product 64
columns wide costs the same).

A grid step is ``CHUNKS`` chunks of one (batch row, group). ``ssd_fwd``
walks the chunks in order with the states in VMEM and writes the states the
step started from; ``ssd_bwd`` walks the steps backwards: it rebuilds each
chunk's entry state from the step's, then carries ``dS`` back through the
chunks. Matmul operands are in ``x``'s dtype (bf16 on the MXU, float32 at
``HIGHEST`` for float32 inputs); decays, ``dt``, the state and every
accumulator are float32.

Layout: ``x, y`` (b, T, H P) and ``B, C`` (b, T, G N) as the convolution
leaves them, a group a lane block picked by the index map; ``dt`` and ``g``
(b, H, n, Q) float32, a chunk a row (the wrappers below lay them out and
take the cumulative sum: ``T`` tiny float32 numbers a head); ``D`` over the
lanes of its head, (G, 1, heads P / G).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
CHUNKS = 8          # chunks a grid step
_VMEM = 64 * 2 ** 20
NN, NT, TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
_F32 = jnp.float32


def _mm(a, b, dims, precision=None):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                               preferred_element_type=_F32)


def _precision(dtype):
    """float32 operands take float32 passes; bf16 operands one pass."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _column(x_row, eye):
    """(1, Q) along the lanes -> (Q, 1) along the sublanes."""
    return jnp.sum(jnp.where(eye, x_row, 0.0), axis=1, keepdims=True)


def _row(x_col, eye):
    """(Q, 1) -> (1, Q)."""
    return jnp.sum(jnp.where(eye, x_col, 0.0), axis=0, keepdims=True)


def _total(x):
    """The sum of everything in ``x`` as (1, 1)."""
    return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1, keepdims=True)


def _fold(x):
    """(R, c) -> (8, c): the row groups of eight added up, vreg onto vreg."""
    return sum(x[i:i + 8] for i in range(0, x.shape[0], 8))


def _by_head(values, head):
    """Per-head (r, 1) values laid over the lanes of their heads: (r, 128)."""
    out = values[0]
    for j, v in enumerate(values[1:], 1):
        out = jnp.where(head == j, v, out)
    return out


def _only(x, head, j, heads):
    """``x`` with the lanes of the other heads of the tile zeroed."""
    return x if heads == 1 else jnp.where(head == j, x, jnp.zeros_like(x))


def _head(g_row, dt_row, row, col):
    """One head's factors in one chunk, float32, but the decay matrix: the
    columns ``e^g`` and ``w``, the whole chunk's decay ``gam`` (1, 1)."""
    eye = row == col
    Q = row.shape[0]
    g_col, dt_col = _column(g_row, eye), _column(dt_row, eye)
    gl = jnp.sum(jnp.where(col[:1] == Q - 1, g_row, 0.0), axis=1, keepdims=True)
    tail = jnp.exp(gl - g_col)
    return dict(g_row=g_row, g_col=g_col, dt_row=dt_row, eg=jnp.exp(g_col), tail=tail,
                w=tail * dt_col, gam=jnp.exp(gl))


def _decay(h, row, col):
    """``L_ij = e^{g_i - g_j}`` on and under the diagonal, 0 above it."""
    return jnp.exp(jnp.where(row >= col, h["g_col"] - h["g_row"], -jnp.inf))


def _masks(Q):
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return row, col


def _tile_heads(g_ref, dt_ref, t, one, hp, row, col):
    """The factors of the ``hp`` heads of lane tile ``t`` in chunk ``one``,
    and the three that enter tile-wide products laid over the lanes."""
    head = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) // (LANES // hp)
    heads = [_head(g_ref[t * hp + j, one, :], dt_ref[t * hp + j, one, :], row, col)
             for j in range(hp)]
    over = {key: _by_head([h[key] for h in heads], head) for key in ("eg", "w", "gam")}
    return head, heads, over


def _next_state(state, b, x32, over, dtype):
    """``e^{g_Q} S_0 + B^T (w x)`` of one lane tile."""
    return state * over["gam"] + _mm(b, (x32 * over["w"]).astype(dtype), TN, _precision(dtype))


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, g_ref, d_ref, y_ref, s0_ref, s_scr, *,
                chunks, Q, hp):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    s0_ref[...] = s_scr[...]
    dtype = x_ref.dtype
    pr = _precision(dtype)
    row, col = _masks(Q)

    def walk(c, carry):
        rows = pl.ds(pl.multiple_of(c * Q, Q), Q)
        one = pl.ds(c, 1)
        b, cc = b_ref[rows, :], c_ref[rows, :]
        cb = _mm(cc, b, NT, pr)
        for t in range(s_scr.shape[0]):
            lanes = slice(t * LANES, (t + 1) * LANES)
            head, heads, over = _tile_heads(g_ref, dt_ref, t, one, hp, row, col)
            x = x_ref[rows, lanes]
            x32 = x.astype(_F32)
            state = s_scr[t]
            y = over["eg"] * _mm(cc, state.astype(dtype), NN, pr) + d_ref[:, lanes] * x32
            for j, h in enumerate(heads):
                m = (cb * _decay(h, row, col) * h["dt_row"]).astype(dtype)
                y = y + _mm(m, _only(x, head, j, hp), NN, pr)
            y_ref[rows, lanes] = y.astype(y_ref.dtype)
            s_scr[t] = _next_state(state, b, x32, over, dtype)
        return carry

    jax.lax.fori_loop(0, chunks, walk, 0)


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, g_ref, d_ref, s0_ref, dy_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dg_ref, dd_ref, ds_scr, states_scr, *,
                chunks, Q, hp):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    dtype = x_ref.dtype
    pr = _precision(dtype)
    lo = lambda z: z.astype(dtype)  # noqa: E731
    tiles = ds_scr.shape[0]
    row, col = _masks(Q)
    eye = row == col
    lanes_sum = lambda z: jnp.sum(z, axis=1, keepdims=True)  # noqa: E731

    states_scr[0] = s0_ref[...]

    def rebuild(c, carry):                 # the state every chunk of the step starts from
        rows = pl.ds(pl.multiple_of(c * Q, Q), Q)
        for t in range(tiles):
            _, _, over = _tile_heads(g_ref, dt_ref, t, pl.ds(c, 1), hp, row, col)
            x32 = x_ref[rows, t * LANES:(t + 1) * LANES].astype(_F32)
            states_scr[c + 1, t] = _next_state(states_scr[c, t], b_ref[rows, :], x32, over, dtype)
        return carry

    jax.lax.fori_loop(0, chunks - 1, rebuild, 0)

    def back(i, carry):                    # dS through the chunks, last to first
        c = chunks - 1 - i
        rows = pl.ds(pl.multiple_of(c * Q, Q), Q)
        one = pl.ds(c, 1)
        b, cc = b_ref[rows, :], c_ref[rows, :]
        cb = _mm(cc, b, NT, pr)
        dcb = jnp.zeros((Q, Q), _F32)
        db = jnp.zeros(b.shape, _F32)
        dc = jnp.zeros(b.shape, _F32)
        for t in range(tiles):
            lanes = slice(t * LANES, (t + 1) * LANES)
            head, heads, over = _tile_heads(g_ref, dt_ref, t, one, hp, row, col)
            x, dy = x_ref[rows, lanes], dy_ref[rows, lanes]
            x32, dy32 = x.astype(_F32), dy.astype(_F32)
            s0, ds1 = states_scr[c, t], ds_scr[t]
            s0_lo, ds1_lo = lo(s0), lo(ds1)
            y2 = over["eg"] * _mm(cc, s0_lo, NN, pr)          # the entry state's part of y
            bds = _mm(b, ds1_lo, NN, pr)                      # d (w x)
            dye = lo(dy32 * over["eg"])
            dc = dc + _mm(dye, s0_lo, NT, pr)
            db = db + _mm(lo(x32 * over["w"]), ds1_lo, NT, pr)
            dx = d_ref[:, lanes] * dy32 + over["w"] * bds
            dyy, xbds, dss = dy32 * y2, x32 * bds, ds1 * s0
            for j, h in enumerate(heads):
                mine = lambda z: _only(z, head, j, hp)  # noqa: E731
                decay = _decay(h, row, col)
                ld = decay * h["dt_row"]
                dm = jnp.where(row >= col, _mm(mine(dy), x, NT, pr), 0.0)
                e = dm * cb * ld
                dcb = dcb + dm * ld
                dw_col = lanes_sum(mine(xbds))
                dww = dw_col * h["w"]
                dg_col = lanes_sum(e) + lanes_sum(mine(dyy)) - dww
                dgl = _total(mine(dss)) * h["gam"] + jnp.sum(dww, axis=0, keepdims=True)
                dg_ref[t * hp + j, one, :] = (
                    _row(dg_col, eye) - jnp.sum(e, axis=0, keepdims=True)
                    + jnp.where(col[:1] == Q - 1, dgl, 0.0))
                ddt_ref[t * hp + j, one, :] = (
                    jnp.sum(dm * cb * decay, axis=0, keepdims=True)
                    + _row(dw_col * h["tail"], eye))
                dx = dx + _mm(lo(cb * ld), mine(dy), TN, pr)
            dx_ref[rows, lanes] = dx.astype(dx_ref.dtype)
            ds_scr[t] = ds1 * over["gam"] + _mm(cc, dye, TN, pr)
            dd_ref[:, lanes] += _fold(dy32 * x32)
        dcb_lo = lo(dcb)
        db_ref[rows, :] = (db + _mm(dcb_lo, cc, TN, pr)).astype(db_ref.dtype)
        dc_ref[rows, :] = (dc + _mm(dcb_lo, b, NN, pr)).astype(dc_ref.dtype)
        return carry

    jax.lax.fori_loop(0, chunks, back, 0)


def _specs(x, dt, B, groups, backwards):
    """Block specs over the grid (batch, group, step of chunks);
    ``backwards`` walks the steps from the last."""
    b, H, n, Q = dt.shape
    chunks = min(n, CHUNKS)
    order = (lambda t: n // chunks - 1 - t) if backwards else (lambda t: t)
    hg, N = H // groups, B.shape[-1] // groups
    width = x.shape[-1] // groups
    wide = pl.BlockSpec((None, chunks * Q, width), lambda i, g, t: (i, order(t), g))
    bc = pl.BlockSpec((None, chunks * Q, N), lambda i, g, t: (i, order(t), g))
    per_chunk = pl.BlockSpec((None, hg, chunks, Q), lambda i, g, t: (i, g, order(t), 0))
    d = pl.BlockSpec((None, 1, width), lambda i, g, t: (g, 0, 0))
    s0 = pl.BlockSpec((None, None, None, width // LANES, N, LANES),
                      lambda i, g, t: (i, g, order(t), 0, 0, 0))
    return chunks, n // chunks, width // LANES, N, wide, bc, per_chunk, d, s0


def _laid(dt, A, Q):
    """``dt`` (b, T, H) float32 -> (b, H, n, Q), and the cumulative log decay
    ``A cumsum(dt)`` inside each chunk beside it."""
    b, T, H = dt.shape
    laid = jnp.moveaxis(dt, 1, 2).reshape(b, H, T // Q, Q)
    cs = jnp.cumsum(laid, axis=-1)
    return laid, cs, A.astype(_F32)[None, :, None, None] * cs


def _d_lanes(D, groups, P):
    """``D`` (H,) over the lanes of its head: (G, 1, heads P / G) float32."""
    return jnp.repeat(D.astype(_F32), P).reshape(groups, 1, -1)


def ssd_fwd(x, dt, A, B, C, D, *, groups, chunk, interpret=False):
    """``y`` (b, T, H P) in ``x``'s dtype and the states every step of chunks
    started from, (b, G, steps, lane tiles a group, N, 128) float32. ``x``
    (b, T, H P); ``dt`` (b, T, H) float32, positive (0: a token that neither
    decays nor writes); ``A`` (H,) negative; ``B``, ``C`` (b, T, G N); ``D``
    (H,). ``T`` in whole steps of chunks."""
    b, T, H = dt.shape
    P = x.shape[-1] // H
    laid, _, g = _laid(dt, A, chunk)
    chunks, nt, tiles, N, wide, bc, per_chunk, d, s0 = _specs(x, laid, B, groups, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunks=chunks, Q=chunk, hp=LANES // P),
        name="ssd_fwd",
        grid=(b, groups, nt),
        in_specs=[wide, bc, bc, per_chunk, per_chunk, d],
        out_specs=[wide, s0],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, groups, nt, tiles, N, LANES), _F32)],
        scratch_shapes=[pltpu.VMEM((tiles, N, LANES), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM),
        interpret=interpret,
    )(x, B, C, laid, g, _d_lanes(D, groups, P))


def ssd_bwd(x, dt, A, B, C, D, s0, dy, *, groups, chunk, interpret=False):
    """Cotangents of (x, dt, A, B, C, D) in their shapes and dtypes."""
    b, T, H = dt.shape
    P = x.shape[-1] // H
    laid, cs, g = _laid(dt, A, chunk)
    chunks, nt, tiles, N, wide, bc, per_chunk, d, s0_spec = _specs(x, laid, B, groups, True)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    width = x.shape[-1] // groups
    dx, dB, dC, ddt, dg, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, chunks=chunks, Q=chunk, hp=LANES // P),
        name="ssd_bwd",
        grid=(b, groups, nt),
        in_specs=[wide, bc, bc, per_chunk, per_chunk, d, s0_spec, wide],
        out_specs=[wide, bc, bc, per_chunk, per_chunk,
                   pl.BlockSpec((None, None, 8, width), lambda i, g, t: (i, g, 0, 0))],
        out_shape=[like(x), like(B), like(C), like(laid), like(laid),
                   jax.ShapeDtypeStruct((b, groups, 8, width), _F32)],
        scratch_shapes=[pltpu.VMEM((tiles, N, LANES), _F32),                 # dS
                        pltpu.VMEM((chunks, tiles, N, LANES), _F32)],   # the chunks' entry states
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM),
        interpret=interpret,
    )(x, B, C, laid, g, _d_lanes(D, groups, P), s0, dy)
    # g = A cumsum(dt) inside a chunk: its cotangent back onto dt and A
    a = A.astype(_F32)[None, :, None, None]
    dcs = dg * a
    ddt = ddt + jnp.flip(jnp.cumsum(jnp.flip(dcs, -1), axis=-1), -1)
    dA = jnp.sum(dg * cs, axis=(0, 2, 3)).astype(A.dtype)
    dD = jnp.sum(dd.reshape(b, groups, 8, H // groups, P), axis=(0, 2, 4)).reshape(H)
    return (dx, jnp.moveaxis(ddt.reshape(b, H, T), 1, 2).astype(dt.dtype), dA, dB, dC,
            dD.astype(D.dtype))
