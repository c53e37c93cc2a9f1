"""Pallas kernels of the chunked gated delta rule: the part that is
sequential in time.

``ops.gated_delta_rule`` turns every chunk of ``C`` tokens into its WY
operands (all chunks at once, in XLA): ``w`` (C, dk), ``u`` (C, dv), the
decayed queries ``qg`` and keys ``kg`` (C, dk), the masked, decayed scores
``p`` (C, C) and the chunk's whole decay ``gam``. What is left walks the
chunks in order with the state ``S (dk, dv)`` in float32:

    v' = u - w S;   o = qg S + p v';   S <- gam S + kg^T v'

``gdn_fwd`` keeps ``S`` in VMEM across the blocks of one (batch, head) row
and handles ``chunks`` chunks a grid step (one DMA of ``chunks * C`` rows an
operand); it writes the state each block started from. ``gdn_bwd`` walks the
blocks backwards: it rebuilds the block's states and ``v'`` from that
starting state, then carries ``dS`` back through the chunks. Matmul operands
are in the operands' dtype (bf16 on the MXU), every accumulator is float32.

Layout: operands (rows = batch x heads, T, feature), one row a grid row. The
per-chunk decay rides as (rows, chunks, 128) float32, the scalar repeated
over the lanes; its gradient comes back as lane-partial sums in the same
shape (the caller's broadcast sums them).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _mm(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


NN, NT, TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _chunk_forward(state, w, u, qg, kg, p, gam_row):
    """One chunk: (new state, v', o), all float32."""
    s = state.astype(w.dtype)
    v_new = u.astype(jnp.float32) - _mm(w, s, NN)
    o = _mm(qg, s, NN) + _mm(p, v_new.astype(p.dtype), NN)
    state = state * gam_row + _mm(kg, v_new.astype(kg.dtype), TN)
    return state, v_new, o


def _fwd_kernel(w_ref, u_ref, qg_ref, kg_ref, p_ref, gam_ref, o_ref, s0_ref,
                s_scr, *, chunks, C):
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    s0_ref[...] = s_scr[...]

    def body(c, carry):
        rows = pl.ds(pl.multiple_of(c * C, C), C)
        state, _, o = _chunk_forward(
            s_scr[...], w_ref[rows, :], u_ref[rows, :], qg_ref[rows, :],
            kg_ref[rows, :], p_ref[rows, :], gam_ref[pl.ds(c, 1), :])
        s_scr[...] = state
        o_ref[rows, :] = o.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, chunks, body, 0)


def _bwd_kernel(w_ref, u_ref, qg_ref, kg_ref, p_ref, gam_ref, s0_ref, do_ref,
                dw_ref, du_ref, dqg_ref, dkg_ref, dp_ref, dgam_ref,
                ds_scr, states_scr, vnew_scr, *, chunks, C):
    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    def rebuild(c, state):
        rows = pl.ds(pl.multiple_of(c * C, C), C)
        states_scr[c] = state
        state, v_new, _ = _chunk_forward(
            state, w_ref[rows, :], u_ref[rows, :], qg_ref[rows, :],
            kg_ref[rows, :], p_ref[rows, :], gam_ref[pl.ds(c, 1), :])
        vnew_scr[rows, :] = v_new
        return state

    jax.lax.fori_loop(0, chunks, rebuild, s0_ref[...])

    def back(i, carry):
        c = chunks - 1 - i
        rows = pl.ds(pl.multiple_of(c * C, C), C)
        w, qg, kg, p = w_ref[rows, :], qg_ref[rows, :], kg_ref[rows, :], p_ref[rows, :]
        do = do_ref[rows, :]
        dt = w.dtype
        gam_row = gam_ref[pl.ds(c, 1), :]
        state, ds = states_scr[c], ds_scr[...]
        s, ds_lo = state.astype(dt), ds.astype(dt)
        v_new = vnew_scr[rows, :]
        dv_new = _mm(p, do, TN) + _mm(kg, ds_lo, NN)           # (C, dv)
        dv_lo = dv_new.astype(dt)
        dp_ref[rows, :] = _mm(do, v_new.astype(dt), NT).astype(dp_ref.dtype)
        dqg_ref[rows, :] = _mm(do, s, NT).astype(dqg_ref.dtype)
        dkg_ref[rows, :] = _mm(v_new.astype(dt), ds_lo, NT).astype(dkg_ref.dtype)
        du_ref[rows, :] = dv_new.astype(du_ref.dtype)
        dw_ref[rows, :] = (-_mm(dv_lo, s, NT)).astype(dw_ref.dtype)
        dgam_ref[pl.ds(c, 1), :] = jnp.sum(state * ds, axis=0, keepdims=True)
        ds_scr[...] = ds * gam_row + _mm(qg, do, TN) - _mm(w, dv_lo, TN)
        return carry

    jax.lax.fori_loop(0, chunks, back, 0)


def chunks_per_step(n_chunks, want=8):
    """Chunks one grid step handles: the largest divisor of ``n_chunks`` up
    to ``want`` whose decay block keeps to Mosaic's tiling (8 chunks, or all
    of them)."""
    if n_chunks <= want:
        return n_chunks
    return want if n_chunks % want == 0 else 0


def _specs(chunks, C, dk, dv, order):
    block = lambda width: pl.BlockSpec((None, chunks * C, width),  # noqa: E731
                                       lambda r, t: (r, order(t), 0))
    gam = pl.BlockSpec((None, chunks, LANES), lambda r, t: (r, order(t), 0))
    s0 = pl.BlockSpec((None, None, dk, dv), lambda r, t: (r, order(t), 0, 0))
    return block, gam, s0


def gdn_fwd(w, u, qg, kg, p, gam, *, interpret=False):
    """o (rows, T, dv) and the state every block of ``chunks`` chunks started
    from (rows, T / (chunks C), dk, dv) float32."""
    R, T, dk = w.shape
    dv, C = u.shape[-1], p.shape[-1]
    chunks = chunks_per_step(T // C)
    nt = T // (chunks * C)
    block, gam_spec, s0_spec = _specs(chunks, C, dk, dv, lambda t: t)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunks=chunks, C=C),
        name="gdn_fwd",
        grid=(R, nt),
        in_specs=[block(dk), block(dv), block(dk), block(dk), block(C), gam_spec],
        out_specs=[block(dv), s0_spec],
        out_shape=[jax.ShapeDtypeStruct((R, T, dv), u.dtype),
                   jax.ShapeDtypeStruct((R, nt, dk, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(w, u, qg, kg, p, gam)


def gdn_bwd(w, u, qg, kg, p, gam, s0, do, *, interpret=False):
    """Cotangents of (w, u, qg, kg, p, gam) in their shapes and dtypes; the
    decay's as lane-partial sums."""
    R, T, dk = w.shape
    dv, C = u.shape[-1], p.shape[-1]
    chunks = chunks_per_step(T // C)
    nt = T // (chunks * C)
    block, gam_spec, s0_spec = _specs(chunks, C, dk, dv, lambda t: nt - 1 - t)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunks=chunks, C=C),
        name="gdn_bwd",
        grid=(R, nt),
        in_specs=[block(dk), block(dv), block(dk), block(dk), block(C), gam_spec,
                  s0_spec, block(dv)],
        out_specs=[block(dk), block(dv), block(dk), block(dk), block(C), gam_spec],
        out_shape=[like(w), like(u), like(qg), like(kg), like(p), like(gam)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32),
                        pltpu.VMEM((chunks, dk, dv), jnp.float32),
                        pltpu.VMEM((chunks * C, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(w, u, qg, kg, p, gam, s0, do)
