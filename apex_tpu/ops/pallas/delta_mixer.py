"""Pallas kernels of the two memory-bound stages that stand around the gated
delta rule in a decoder layer: every large array crosses HBM once a pass in
the model's dtype, and float32 lives in VMEM only.

``conv_silu_fwd`` / ``conv_silu_bwd`` — the depthwise causal convolution over
time (``taps`` rows, the last tap on the current token; with ``bias`` a
channel's constant is added before the SiLU) and SiLU, over
``width`` channels of ``x`` (b, T, C) from channel ``start`` on: a fused
projection is read in place, its other columns are never touched. A grid
step is one (batch row, channel block, time block); the rows before a time
block come from a second, eight- or sixteen-row view of the same array
(zeros at a row's start), and inside a block a loop walks strips of rows
with the last eight rows of the strip before in registers, so that the
shifted operands are sublane rolls of what is already there. The backward
walks time blocks and strips from the end: it recomputes the convolution,
carries the first rows of ``d pre`` of the strip after (a VMEM scratch
between grid steps), writes ``dx`` once and sums the taps' gradient in a
float32 output block the time axis revisits (a partial a batch row; the
caller adds them).

``gated_norm_fwd`` / ``gated_norm_bwd`` — ``rmsnorm(o) * w * silu(z)`` a head
of ``dim`` lanes over rows (N, heads dim); ``z`` is read in place from
channel ``start`` of a wider (N, C) array. The backward recomputes the
statistics from ``o`` (one lane reduction a row and head) and sums the
weight's gradient as the convolution does. ``gate_first`` gates before it
normalises, ``rmsnorm(o * silu(z)) * w``; a weight (1, C) wide is a weight a
channel instead of one shared by the heads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.pallas import exact_block

LANES = 128
# (rows a grid step at most, channels a grid step where the widths allow, rows
# a pass of the loop inside a block), by the chip's clock at (2, 8192, 8192)
# and (16384, 4096) bf16: the norm wants four heads a pass in flight (its lane
# reductions wait on each other), the convolution long blocks (fewer halos)
CONV_BLOCK = (2048, 256, 32)
NORM_BLOCK = (1024, 512, 32)
HALO = 8            # rows kept from the strip before (or after): taps <= HALO + 1
_F32 = jnp.float32


def sublanes(dtype) -> int:
    """Rows of one tile of ``dtype``: a block's rows come in whole tiles."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _blocks(block, rows, dtype, *channels):
    """(rows a grid step, rows a strip, channels a grid step): ``block``
    (whose rows are counted in two-byte elements: the same VMEM for any
    dtype) where it divides ``rows`` and every one of ``channels``, else
    less."""
    tb = exact_block(rows, block[0] * sublanes(dtype) // 16, sublanes(dtype))
    strip = exact_block(tb, block[2], sublanes(dtype))
    cb = block[1]
    while any(c % cb for c in channels):
        cb //= 2
    return tb, strip, cb


def _fold(x):
    """(R, c) -> (8, c): the row groups of eight added up, vreg onto vreg."""
    return sum(x[i:i + 8] for i in range(0, x.shape[0], 8))


def _before(x, tail, d):
    """``x`` (R, c) moved ``d`` rows down, the ``d`` rows that enter at the
    top from ``tail`` (the 8 rows before ``x``)."""
    return x if d == 0 else pltpu.roll(jnp.concatenate([tail, x]), d, 0)[HALO:]


def _after(x, head, d):
    """``x`` moved ``d`` rows up, the rows that enter at the bottom from
    ``head`` (the 8 rows after ``x``)."""
    if d == 0:
        return x
    n = x.shape[0] + HALO
    return pltpu.roll(jnp.concatenate([x, head]), n - d, 0)[:x.shape[0]]


def _taps(w_ref):
    return [w_ref[j:j + 1, :] for j in range(w_ref.shape[0])]


def _dot(w, xs):
    """``sum_j w[j] xs[j]``: (1, c) taps on (R, c) operands."""
    total = w[0] * xs[0]
    for a, b in zip(w[1:], xs[1:]):
        total = total + a * b
    return total


def _shifted(x, tail, taps):
    """The convolution's operands, tap by tap: ``x`` moved ``taps - 1 - j``
    rows down."""
    return [_before(x, tail, taps - 1 - j) for j in range(taps)]


def _last_rows(ref, start=0):
    """The ``HALO`` float32 rows that end a tile of ``ref`` which starts at
    row ``start``."""
    size = sublanes(ref.dtype)
    return ref[pl.ds(start, size), :].astype(_F32)[size - HALO:]


def _conv_fwd_kernel(halo_ref, x_ref, w_ref, *rest, strip):
    *bias, y_ref = rest                    # the bias's (1, c) block, where there is one
    w = _taps(w_ref)
    tail = jnp.where(pl.program_id(2) == 0, 0.0, _last_rows(halo_ref))

    def walk(s, tail):
        rows = pl.ds(pl.multiple_of(s * strip, strip), strip)
        x = x_ref[rows, :].astype(_F32)
        pre = _dot(w, _shifted(x, tail, len(w)))
        if bias:
            pre = pre + bias[0][...]
        y_ref[rows, :] = (pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)
        return x[strip - HALO:]

    jax.lax.fori_loop(0, x_ref.shape[0] // strip, walk, tail)


def _conv_bwd_kernel(halo_ref, x_ref, w_ref, *rest, strip):
    # with a bias: its (1, c) block after the taps, and its gradient (the
    # sum of ``d pre``) as one more row of ``dw``
    *bias, dy_ref, dx_ref, dw_ref, head_scr = rest
    t, nt = pl.program_id(2), pl.num_programs(2)

    @pl.when(t == 0)                       # the row's last block: nothing after it
    def _():
        head_scr[...] = jnp.zeros_like(head_scr)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    w = _taps(w_ref)
    taps, n, sub = len(w), x_ref.shape[0] // strip, sublanes(x_ref.dtype)
    halo = jnp.where(t == nt - 1, 0.0, _last_rows(halo_ref))

    def walk(i, carry):
        head, sums = carry
        s = n - 1 - i
        rows = pl.ds(pl.multiple_of(s * strip, strip), strip)
        x = x_ref[rows, :].astype(_F32)
        inside = _last_rows(x_ref, pl.multiple_of(jnp.maximum(s * strip - sub, 0), sub))
        xs = _shifted(x, jnp.where(s == 0, halo, inside), taps)
        pre = _dot(w, xs)
        if bias:
            pre = pre + bias[0][...]
        sig = jax.nn.sigmoid(pre)
        dpre = dy_ref[rows, :].astype(_F32) * (sig * (1.0 + pre * (1.0 - sig)))
        dx = _dot(w, [_after(dpre, head, taps - 1 - j) for j in range(taps)])
        dx_ref[rows, :] = dx.astype(dx_ref.dtype)
        folded = tuple(a + _fold(dpre * b) for a, b in zip(sums, xs))
        if bias:
            folded += (sums[taps] + _fold(dpre),)
        return dpre[:HALO], folded

    zeros = jnp.zeros((8, x_ref.shape[1]), _F32)
    head, sums = jax.lax.fori_loop(0, n, walk, (head_scr[...], (zeros,) * dw_ref.shape[0]))
    head_scr[...] = head
    for j in range(dw_ref.shape[0]):
        dw_ref[j] += sums[j]


def _conv_specs(x, w, start, backwards):
    """Grid (batch, channel block, time block), the strip and the channel
    block, and the views of ``x`` (the tile before a block, the block), of
    the taps and of a (b, T, width) array; ``backwards`` walks the time
    blocks from the end."""
    (b, T, _), (taps, width) = x.shape, w.shape
    tb, strip, cb = _blocks(CONV_BLOCK, T, x.dtype, width, start)
    sub, first, nt = sublanes(x.dtype), start // cb, T // tb
    order = (lambda t: nt - 1 - t) if backwards else (lambda t: t)
    block = pl.BlockSpec((None, tb, cb), lambda i, c, t: (i, order(t), c))
    halo = pl.BlockSpec((None, sub, cb), lambda i, c, t: (
        i, jnp.maximum(order(t) * (tb // sub) - 1, 0), first + c))
    here = pl.BlockSpec((None, tb, cb), lambda i, c, t: (i, order(t), first + c))
    weights = pl.BlockSpec((taps, cb), lambda i, c, t: (0, c))
    return (b, width // cb, nt), strip, cb, [halo, here, weights], block


def _bias_spec(cb):
    return pl.BlockSpec((1, cb), lambda i, c, t: (0, c))


def conv_silu_fwd(x, w, bias=None, *, start=0, interpret=False):
    """``silu(conv(x[..., start:start + width]) + bias)`` (b, T, width) in
    ``x``'s dtype; ``w`` (taps, width), ``bias`` (1, width) or None, float32."""
    grid, strip, cb, inputs, block = _conv_specs(x, w, start, False)
    more = () if bias is None else (bias,)
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, strip=strip),
        name="conv_silu_fwd",
        grid=grid,
        in_specs=inputs + [_bias_spec(cb)] * len(more),
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape[:2] + w.shape[1:], x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(x, x, w, *more)


def conv_silu_bwd(x, w, dy, bias=None, *, start=0, interpret=False):
    """``dx`` (b, T, width) in ``x``'s dtype and the taps' gradient as
    partial sums (b, taps, 8, width) float32 — with a ``bias``, its gradient
    as one more row after the taps'."""
    taps, width = w.shape
    grid, strip, cb, inputs, block = _conv_specs(x, w, start, True)
    more = () if bias is None else (bias,)
    taps += len(more)
    return pl.pallas_call(
        functools.partial(_conv_bwd_kernel, strip=strip),
        name="conv_silu_bwd",
        grid=grid,
        in_specs=inputs + [_bias_spec(cb)] * len(more) + [block],
        out_specs=[block, pl.BlockSpec((None, taps, 8, cb), lambda i, c, t: (i, 0, 0, c))],
        out_shape=[jax.ShapeDtypeStruct(dy.shape, x.dtype),
                   jax.ShapeDtypeStruct((x.shape[0], taps, 8, width), _F32)],
        scratch_shapes=[pltpu.VMEM((HALO, cb), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, x, w, *more, dy)


def _heads(o_ref, z_ref, rows, dim):
    """(lanes, o, z) float32 of every head of the block, strip ``rows``."""
    for h in range(o_ref.shape[1] // dim):
        lanes = slice(h * dim, (h + 1) * dim)
        yield lanes, o_ref[rows, lanes].astype(_F32), z_ref[rows, lanes].astype(_F32)


def _weight_of(w, dim):
    """``lanes -> the weight of those lanes``: a weight (1, dim) is every
    head's, a wider one holds a channel's own."""
    return (lambda lanes: w) if w.shape[1] == dim else (lambda lanes: w[:, lanes])


def _norm_fwd_kernel(o_ref, z_ref, w_ref, y_ref, *, strip, dim, eps, gate_first=False):
    w = _weight_of(w_ref[...], dim)

    def walk(s, carry):
        rows = pl.ds(pl.multiple_of(s * strip, strip), strip)
        for lanes, o, z in _heads(o_ref, z_ref, rows, dim):
            silu = z * jax.nn.sigmoid(z)
            if gate_first:
                o = o * silu
            r = jax.lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + eps)
            y = o * r * w(lanes)
            y_ref[rows, lanes] = (y if gate_first else y * silu).astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, o_ref.shape[0] // strip, walk, 0)


def _norm_bwd_kernel(o_ref, z_ref, w_ref, dy_ref, do_ref, dz_ref, dw_ref, *, strip, dim, eps,
                     gate_first=False):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    if gate_first or w_ref.shape[1] != dim:     # the default keeps the body it always had
        return _norm_bwd_general(o_ref, z_ref, w_ref, dy_ref, do_ref, dz_ref, dw_ref,
                                    strip=strip, dim=dim, eps=eps, gate_first=gate_first)
    w = w_ref[...]

    def walk(s, total):
        rows = pl.ds(pl.multiple_of(s * strip, strip), strip)
        for lanes, o, z in _heads(o_ref, z_ref, rows, dim):
            dy = dy_ref[rows, lanes].astype(_F32)
            r = jax.lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + eps)
            n = o * r
            sig = jax.nn.sigmoid(z)
            gated = dy * (z * sig)                       # d (n w)
            dn = gated * w
            do = r * (dn - n * jnp.mean(dn * n, axis=1, keepdims=True))
            do_ref[rows, lanes] = do.astype(do_ref.dtype)
            dz_ref[rows, lanes] = (dy * (n * w) * (sig * (1.0 + z * (1.0 - sig)))
                                   ).astype(dz_ref.dtype)
            total = total + _fold(gated * n)
        return total

    dw_ref[...] += jax.lax.fori_loop(0, o_ref.shape[0] // strip, walk, jnp.zeros((8, dim), _F32))


def _norm_bwd_general(o_ref, z_ref, w_ref, dy_ref, do_ref, dz_ref, dw_ref, *, strip, dim, eps,
                         gate_first):
    """The backward in either order of gate and norm, for a weight a channel
    or one a head; ``dw`` a head's lanes at a time."""
    w = _weight_of(w_ref[...], dim)
    each = w_ref.shape[1] != dim
    heads = o_ref.shape[1] // dim

    def walk(s, totals):
        rows = pl.ds(pl.multiple_of(s * strip, strip), strip)
        out = []
        for (lanes, o, z), total in zip(_heads(o_ref, z_ref, rows, dim), totals):
            dy = dy_ref[rows, lanes].astype(_F32)
            sig = jax.nn.sigmoid(z)
            silu, dsilu = z * sig, sig * (1.0 + z * (1.0 - sig))
            u = o * silu if gate_first else o
            r = jax.lax.rsqrt(jnp.mean(u * u, axis=1, keepdims=True) + eps)
            n = u * r
            gated = dy if gate_first else dy * silu          # d (n w)
            dn = gated * w(lanes)
            du = r * (dn - n * jnp.mean(dn * n, axis=1, keepdims=True))
            if gate_first:
                do_ref[rows, lanes] = (du * silu).astype(do_ref.dtype)
                dz_ref[rows, lanes] = (du * o * dsilu).astype(dz_ref.dtype)
            else:
                do_ref[rows, lanes] = du.astype(do_ref.dtype)
                dz_ref[rows, lanes] = (dy * (n * w(lanes)) * dsilu).astype(dz_ref.dtype)
            out.append(total + _fold(gated * n))
        return tuple(out)

    totals = jax.lax.fori_loop(0, o_ref.shape[0] // strip, walk,
                               (jnp.zeros((8, dim), _F32),) * heads)
    dw_ref[...] += jnp.concatenate(totals, axis=1) if each and heads > 1 else (
        totals[0] if each or heads == 1 else sum(totals))


def _norm_specs(o, w, dim, start):
    N, C = o.shape
    tb, strip, cb = _blocks(NORM_BLOCK, N, o.dtype, C, start)
    cb = max(cb, dim)                              # whole heads
    first = start // cb
    block = pl.BlockSpec((tb, cb), lambda c, t: (t, c))
    gate = pl.BlockSpec((tb, cb), lambda c, t: (t, first + c))
    if w.shape[1] == dim:                          # one weight for every head
        weight = pl.BlockSpec((1, dim), lambda c, t: (0, 0))
    else:                                          # a weight a channel
        weight = pl.BlockSpec((1, cb), lambda c, t: (0, c))
    return (C // cb, N // tb), strip, block, gate, weight


def gated_norm_fwd(o, z, w, *, start=0, dim, eps, gate_first=False, interpret=False):
    """``rmsnorm(o) * w * silu(z[:, start:start + C])`` a head of ``dim``
    lanes (``gate_first``: ``rmsnorm(o * silu(z)) * w``), (N, C) in ``o``'s
    dtype; ``w`` (1, dim), or (1, C) a channel its own, float32."""
    grid, strip, block, gate, weight = _norm_specs(o, w, dim, start)
    return pl.pallas_call(
        functools.partial(_norm_fwd_kernel, strip=strip, dim=dim, eps=eps,
                          gate_first=gate_first),
        name="gated_norm_fwd",
        grid=grid,
        in_specs=[block, gate, weight],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(o, z, w)


def gated_norm_bwd(o, z, w, dy, *, start=0, dim, eps, gate_first=False, interpret=False):
    """``do``, ``dz`` (N, C) in their inputs' dtypes and the weight's
    gradient as partial sums (channel blocks, 8, the weight's block: ``dim``,
    or a channel block of a weight a channel) float32."""
    grid, strip, block, gate, weight = _norm_specs(o, w, dim, start)
    wide = weight.block_shape[1]
    return pl.pallas_call(
        functools.partial(_norm_bwd_kernel, strip=strip, dim=dim, eps=eps,
                          gate_first=gate_first),
        name="gated_norm_bwd",
        grid=grid,
        in_specs=[block, gate, weight, block],
        out_specs=[block, block, pl.BlockSpec((None, 8, wide), lambda c, t: (c, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype), jax.ShapeDtypeStruct(o.shape, z.dtype),
                   jax.ShapeDtypeStruct((grid[0], 8, wide), _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(o, z, w, dy)
