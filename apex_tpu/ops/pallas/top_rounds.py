"""The k largest of every row by k rounds of a row maximum, where this
compiler turns ``jax.lax.top_k`` into a full stable sort of every row.

Round j takes each row's maximum, the LOWEST index that attains it, and
excludes that index from the later rounds: ``jax.lax.top_k``'s indices, ties
included, for rows of finite scores (where fewer than k are finite an index
would be chosen twice). :func:`top_rounds` is the XLA composition, k passes
over the scores; ``moe_top_rounds`` the kernel: the scores arrive with the
tokens along lanes — (E, T), the layout XLA gives (T, E) scores on its own —
so a round is elementwise work down the E rows of 128 tokens at a time and
the ids leave lane-dense, (k, T).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
COLS = 512              # tokens a grid step


def _rounds(score, k, axis):
    """The k index vectors (``axis`` kept, int32) of ``score``'s k largest
    along ``axis``."""
    width = score.shape[axis]
    place = jax.lax.broadcasted_iota(jnp.int32, score.shape, axis)
    found = []
    for _ in range(k):
        top = jnp.max(score, axis=axis, keepdims=True)
        idx = jnp.min(jnp.where(score == top, place, width), axis=axis, keepdims=True)
        found.append(jnp.minimum(idx, width - 1))        # a row of NaN: in range all the same
        score = jnp.where(place == idx, -jnp.inf, score)
    return found


def top_rounds(score, k):
    """(T, E) float32 -> (T, k) int32, the rounds as XLA operations."""
    return jnp.concatenate(_rounds(score, k, 1), axis=1)


def shapes_ok(tokens, width):
    """Shapes the kernel takes: whole lane tiles of tokens, whole sublane
    tiles of scores."""
    return tokens % LANES == 0 and width % 8 == 0


def _kernel(p_ref, bias_ref, o_ref, *, k):
    for c in range(p_ref.shape[1] // LANES):
        cols = slice(c * LANES, (c + 1) * LANES)
        for j, idx in enumerate(_rounds(p_ref[:, cols] + bias_ref[...], k, 0)):
            o_ref[j:j + 1, cols] = idx


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def moe_top_rounds(p_t, bias, *, k, interpret=False):
    """p_t (E, T) float32, a token a column; bias (E,) float32 -> (k, T)
    int32: the top k of every column of ``p_t + bias``."""
    E, T = p_t.shape
    cols = COLS if T % COLS == 0 else LANES
    return pl.pallas_call(
        functools.partial(_kernel, k=k), name="moe_top_rounds",
        grid=(T // cols,),
        in_specs=[pl.BlockSpec((E, cols), lambda i: (0, i)),
                  pl.BlockSpec((E, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((k, cols), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k, T), jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(p_t, bias.reshape(E, 1))
