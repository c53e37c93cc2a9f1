"""Pallas grouped matrix products for a dropless expert layer.

Rows arrive sorted by expert and laid out so that every tile of ``TM`` rows
belongs to ONE expert (each expert's rows start on a tile boundary; the
padding rows are zeros). A tile is then a plain matmul against its expert's
matrix, chosen by a scalar-prefetched ``tile_expert`` in the weight's index
map; consecutive tiles of one expert find the matrix already in VMEM, so
every held expert's weights cross HBM once a pass. Tiles at or past
``n_used`` do nothing but zero their output.

* ``moe_gmm``     out[tile] = x[tile] @ w[e]
* ``moe_gmm_dx``  dx[tile] = dy[tile] @ w[e].T
* ``moe_gmm_dw``  dw[e] = sum over e's tiles of x[tile].T @ dy[tile]

float32 accumulation, operands in their own dtype. ``dw`` starts from a
zero buffer aliased to the output, so an expert with no tile keeps zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.pallas import exact_block

TM = 128
_VMEM = 64 * 2 ** 20   # of the chip's 128 MiB: a whole (K, N) expert matrix twice


def _gmm_kernel(tile_expert, n_used, x_ref, w_ref, o_ref, *, transpose_w):
    i = pl.program_id(0)

    @pl.when(i < n_used[0])
    def _():
        dims = ((1,), (1,)) if transpose_w else ((1,), (0,))
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[...], (dims, ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(i >= n_used[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _gmm_call(x, w, transpose_w, interpret):
    """What the two product kernels share: one tile of rows a grid step, the
    expert's whole matrix chosen by the prefetched ``tile_expert``."""
    M, K = x.shape
    _, a, b = w.shape
    N = a if transpose_w else b
    return dict(
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(M // TM,),
            in_specs=[pl.BlockSpec((TM, K), lambda i, te, nu: (i, 0)),
                      pl.BlockSpec((None, a, b), lambda i, te, nu: (te[i], 0, 0))],
            out_specs=pl.BlockSpec((TM, N), lambda i, te, nu: (i, 0))),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM),
        interpret=interpret)


def moe_gmm(x, w, tile_expert, n_used, *, interpret=False):
    """x (M, K) in tiles of ``TM`` rows, w (E, K, N), tile_expert (M / TM,)
    int32, n_used (1,) int32 -> (M, N)."""
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_w=False), name="moe_gmm",
        **_gmm_call(x, w, False, interpret))(tile_expert, n_used, x, w)


def moe_gmm_dx(dy, w, tile_expert, n_used, *, interpret=False):
    """dy (M, N), w (E, K, N) -> dx (M, K) = dy[tile] @ w[e].T."""
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_w=True), name="moe_gmm_dx",
        **_gmm_call(dy, w, True, interpret))(tile_expert, n_used, dy, w)


def _dw_kernel(tile_expert, n_used, x_ref, dy_ref, zero_ref, dw_ref, acc, *, tiles):
    del zero_ref
    i = pl.program_id(1)
    e = tile_expert[i]
    used = i < n_used[0]
    first = jnp.logical_or(i == 0, tile_expert[jnp.maximum(i - 1, 0)] != e)
    last = jnp.logical_or(i == n_used[0] - 1,
                          tile_expert[jnp.minimum(i + 1, tiles - 1)] != e)

    @pl.when(jnp.logical_and(used, first))
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(used)
    def _():
        acc[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(used, last))
    def _():
        dw_ref[...] = acc[...].astype(dw_ref.dtype)

    @pl.when(jnp.logical_and(i == 0, jnp.logical_not(used)))
    def _():                                   # no tile at all: the one block visited
        dw_ref[...] = jnp.zeros_like(dw_ref)


def moe_gmm_dw(x, dy, tile_expert, n_used, num_experts, *, interpret=False):
    """x (M, K), dy (M, N) -> dw (E, K, N) in ``x``'s dtype."""
    M, K = x.shape
    N = dy.shape[1]
    # a column block that DIVIDES N (2,816 = 11 x 256): a last partial block
    # would lie outside the grid and its columns would keep the zeros
    bn = exact_block(N, 512, 128) or N
    tiles = M // TM
    # unused tiles keep the last used tile's block, so nothing is fetched or
    # written back for them
    tile = lambda i, nu: jnp.minimum(i, jnp.maximum(nu[0] - 1, 0))  # noqa: E731
    w_block = pl.BlockSpec((None, K, bn), lambda n, i, te, nu: (te[tile(i, nu)], 0, n))
    return pl.pallas_call(
        functools.partial(_dw_kernel, tiles=tiles),
        name="moe_gmm_dw",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N // bn, tiles),
            in_specs=[pl.BlockSpec((TM, K), lambda n, i, te, nu: (tile(i, nu), 0)),
                      pl.BlockSpec((TM, bn), lambda n, i, te, nu: (tile(i, nu), n)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=w_block,
            scratch_shapes=[pltpu.VMEM((K, bn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((num_experts, K, N), x.dtype),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM),
        interpret=interpret,
    )(tile_expert, n_used, x, dy, jnp.zeros((num_experts, K, N), x.dtype))
