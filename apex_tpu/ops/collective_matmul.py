"""Ring-decomposed, compute-overlapped collective matmuls for the TP/SP
boundary patterns.

Inside ``shard_map`` XLA does NOT overlap a boundary collective with the
GEMM it feeds (the latency-hiding scheduler only reorders collectives it
inserted itself, under pjit): ``ColumnParallelLinear``'s all-gather and
``RowParallelLinear``'s reduce-scatter/psum each stall the MXU for the full
boundary latency, twice per linear, forward and backward. This module
hand-decomposes those collectives into per-rank sequence chunks carried by
``lax.ppermute`` steps, matmuling the chunk already on hand while the next
chunk is in flight (Xu et al., arXiv:2004.13336; veScale does the same for
eager SPMD):

* :func:`all_gather_matmul` — ``all_gather(x) @ w.T`` as a bidirectional
  ring: ⌈(tp−1)/2⌉ ``ppermute`` steps, each delivering up to two remote
  chunks whose GEMMs run while the following chunks travel.
* :func:`matmul_reduce_scatter` — ``psum_scatter(x @ w.T)`` as the
  transpose ring: tp steps, each computing ONE destination shard's chunk
  GEMM and folding it into the partial sum arriving from the previous
  rank.
* :func:`matmul_all_reduce` — ``psum(x @ w.T)`` (the non-SP RowParallel
  epilogue) as the reduce-scatter ring above followed by a bidirectional
  chunk all-gather (pure rotation; nothing left to hide).
* :func:`copy_matmul` — the non-SP ColumnParallel pattern: forward is the
  plain local GEMM (``copy_to`` is the identity), backward overlaps the
  ``psum`` of ``g @ w`` the copy's transpose demands.

Custom VJPs pin the transpose pairs exactly as
``tensor_parallel.mappings`` pins the blocking collectives: the transpose
of ag-matmul is matmul-rs and vice versa; ``matmul_all_reduce`` carries the
``reduce_from`` pair (psum forward, identity backward) and ``copy_matmul``
the ``copy_to`` pair (identity forward, psum backward). Every reduction
visits contributions in a FIXED ring order (chunk ``j`` accumulates
``f_{j+1}, f_{j+2}, …, f_j``), so results are deterministic — two runs
produce the same bits — and each output shard is computed once, by one
rank's schedule, so replicated outputs are identical across tp ranks.

Weight-gradient partials accumulate in fp32 (``preferred_element_type``)
and cast to the weight dtype once at the end — the chunked sum otherwise
loses bits the blocking path's single fused GEMM keeps.

All functions take ``seq_dim ∈ {0, 1}`` (the layers' ``(s, b, h)`` /
``(b, s, h)`` layouts), require the axis to be bound (call inside
``shard_map``), and degrade to the plain GEMM at ``axis_name=None`` /
tp=1. The ``matmul_*`` family chunks a FULL-sequence operand and
validates divisibility eagerly with an error naming the knob, instead of
the bare XLA shape error the blocking ``psum_scatter`` dies with.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from apex_tpu.parallel import mesh as mesh_lib


def _count_ppermute(payload, count, axis_name):
    """Trace-time ppermute accounting (cf. pipeline ``_rotate``).
    Lazy-import shim only; the counting contract lives in
    ``monitor.hooks.count_traffic``."""
    from apex_tpu.monitor import hooks as monitor_hooks

    if count > 0:
        monitor_hooks.count_traffic("ppermute", payload, axis_name,
                                    count=count)


def _ring_span(op, payload, axis_name):
    """Trace-time step-anatomy span around one ring decomposition: the
    ring's HLOs (chunk GEMMs + ppermute hops) carry the
    ``<op>_ring_<axis>`` named scope into device traces, and the span
    record carries the per-hop chunk size (``bytes``) for CostDB
    calibration — the hop count rides the ``_count_ppermute`` counters.
    The scope is entered always; the record only while monitoring is on."""
    from apex_tpu.monitor import spans as monitor_spans

    return monitor_spans.collective_span(f"{op}_ring", payload, axis_name)


def _check_operands(x, w, seq_dim, op, *, features_from):
    """Eager shape validation with errors that name the operand and the
    layer knob (``overlap_comm``) instead of a deep-XLA shape mismatch."""
    if not 0 <= seq_dim < x.ndim - 1:
        raise ValueError(
            f"{op}: seq_dim={seq_dim} is not a leading axis of the "
            f"activation (shape {x.shape}; the last axis is features) — "
            f"the layers expose seq_dim=0 for (s, b, h) and 1 for "
            f"(b, s, h)")
    if w.ndim != 2 or w.shape[features_from] != x.shape[-1]:
        raise ValueError(
            f"{op}: weight {w.shape} does not contract with activation "
            f"features {x.shape[-1]} (torch-layout weight expected, "
            f"axis {features_from} = input features)")


def _check_divisible(x, seq_dim, tp, axis_name, op):
    if x.shape[seq_dim] % tp:
        raise ValueError(
            f"{op}: sequence extent {x.shape[seq_dim]} (axis {seq_dim} of "
            f"{x.shape}) is not divisible by the {axis_name!r} axis size "
            f"{tp} — the ring chunks the sequence per rank; pad the "
            f"sequence or turn off overlap_comm/sequence_parallel on "
            f"this linear")


# --- ring cores ---------------------------------------------------------------

def _ring_all_gather_apply(x, chunk_fn, axis_name, seq_dim,
                           acc_fn=None):
    """Bidirectional all-gather ring: deliver every rank's chunk of ``x``
    and write ``chunk_fn(chunk)`` at the chunk's global sequence offset.
    ⌈(tp−1)/2⌉ steps; each delivers two chunks (one per direction) except
    the final step of an even ring, where the directions meet. The GEMM of
    the chunk on hand overlaps the in-flight ``ppermute`` of the next.

    ``acc_fn(acc, chunk, j)`` optionally folds each delivered chunk into a
    side accumulator (the dW ride-along of ``matmul_reduce_scatter``'s
    backward); visit order is local chunk first, then alternating
    fwd/bwd — fixed, so the accumulation is deterministic.

    Returns ``(full-seq output, acc)``.
    """
    tp = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    s_loc = x.shape[seq_dim]
    fwd_perm = [(i, (i + 1) % tp) for i in range(tp)]
    bwd_perm = [(i, (i - 1) % tp) for i in range(tp)]

    y_local = chunk_fn(x)
    out_shape = list(y_local.shape)
    out_shape[seq_dim] = tp * s_loc
    out = jnp.zeros(tuple(out_shape), y_local.dtype)
    out = jax.lax.dynamic_update_slice_in_dim(
        out, y_local, rank * s_loc, axis=seq_dim)
    acc = None if acc_fn is None else acc_fn(None, x, rank)

    steps = (tp - 1 + 1) // 2  # ⌈(tp−1)/2⌉
    n_bwd = steps - 1 if tp % 2 == 0 and tp > 1 else steps
    _count_ppermute(x, steps + n_bwd, axis_name)
    fwd = bwd = x
    for t in range(1, steps + 1):
        fwd = jax.lax.ppermute(fwd, axis_name, fwd_perm)
        jf = (rank - t) % tp
        out = jax.lax.dynamic_update_slice_in_dim(
            out, chunk_fn(fwd), jf * s_loc, axis=seq_dim)
        if acc_fn is not None:
            acc = acc_fn(acc, fwd, jf)
        if t == steps and tp % 2 == 0:
            break  # (rank − t) ≡ (rank + t) (mod tp): directions meet
        bwd = jax.lax.ppermute(bwd, axis_name, bwd_perm)
        jb = (rank + t) % tp
        out = jax.lax.dynamic_update_slice_in_dim(
            out, chunk_fn(bwd), jb * s_loc, axis=seq_dim)
        if acc_fn is not None:
            acc = acc_fn(acc, bwd, jb)
    return out, acc


def _ring_reduce_scatter(contrib_fn, axis_name, *, payload=None,
                         payload_fn=None):
    """Reduce-scatter ring: the accumulator destined for rank ``j`` starts
    at rank ``j+1`` and travels +1, each rank adding its own contribution
    ``contrib_fn(j)`` — the per-chunk GEMM, which depends only on local
    operands, so XLA overlaps it with the arriving partial sum's
    ``ppermute``. Per destination chunk the summation order is the fixed
    ring order ``f_{j+1} + f_{j+2} + … + f_j``.

    ``payload``/``payload_fn`` piggyback a second rotation in the same +1
    direction (the x-chunk ride-along of ``all_gather_matmul``'s backward):
    at step ``t`` the payload holds chunk ``(rank − t) % tp`` and
    ``payload_fn(extra, payload, j2)`` folds it.

    Returns ``(this rank's reduced chunk, extra)``.
    """
    tp = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % tp) for i in range(tp)]
    acc = None
    extra = None
    for t in range(tp):
        j = (rank - 1 - t) % tp
        c = contrib_fn(j)
        if t == 0:
            acc = c
        else:
            acc = jax.lax.ppermute(acc, axis_name, perm) + c
        if payload_fn is not None:
            if t > 0:
                payload = jax.lax.ppermute(payload, axis_name, perm)
            extra = payload_fn(extra, payload, (rank - t) % tp)
    if acc is not None and tp > 1:
        _count_ppermute(acc, tp - 1, axis_name)
        if payload_fn is not None:
            _count_ppermute(payload, tp - 1, axis_name)
    return acc, extra


def _seq_chunk(x, seq_dim, j, s_loc):
    return jax.lax.dynamic_slice_in_dim(x, j * s_loc, s_loc, axis=seq_dim)


def _dw_fold(acc, g_chunk, x_chunk):
    """One chunk's weight-grad partial, accumulated in fp32 (the blocking
    path's single GEMM keeps fp32 accumulation inside the MXU; a chunked
    bf16 sum would not)."""
    part = jnp.einsum("...o,...i->oi", g_chunk, x_chunk,
                      preferred_element_type=jnp.float32)
    return part if acc is None else acc + part


# --- all-gather → matmul ------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _ag_matmul(x, w, axis_name, seq_dim):
    with _ring_span("ag_matmul", x, axis_name):
        y, _ = _ring_all_gather_apply(
            x, lambda c: jnp.dot(c, w.T), axis_name, seq_dim)
    return y


def _ag_matmul_fwd(x, w, axis_name, seq_dim):
    # residuals are the LOCAL shard + weight: the gathered activation is
    # never materialized, forward or backward (the blocking path saves the
    # full (s, …, h) gather as a matmul residual)
    return _ag_matmul(x, w, axis_name, seq_dim), (x, w)


def _ag_matmul_bwd(axis_name, seq_dim, res, g):
    x, w = res
    s_loc = x.shape[seq_dim]

    def contrib(j):  # dx chunk for rank j: local g slice, local w
        return jnp.dot(_seq_chunk(g, seq_dim, j, s_loc), w)

    def dw_ride(acc, x_chunk, j):  # x chunks rotate; g slices are local
        return _dw_fold(acc, _seq_chunk(g, seq_dim, j, s_loc), x_chunk)

    with _ring_span("ag_matmul_bwd", g, axis_name):
        dx, dw = _ring_reduce_scatter(
            contrib, axis_name, payload=x, payload_fn=dw_ride)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_ag_matmul.defvjp(_ag_matmul_fwd, _ag_matmul_bwd)


def all_gather_matmul(x, w, *, axis_name=mesh_lib.TENSOR_AXIS, seq_dim=0):
    """``all_gather(x, seq_dim, tiled) @ w.T`` as a compute-overlapped
    bidirectional ring — the SP ``ColumnParallelLinear`` boundary. ``x`` is
    this rank's sequence shard, ``w`` the torch-layout ``(out_local, in)``
    column shard; returns the full-sequence ``(…, out_local)`` product.
    Backward is the matmul→reduce-scatter ring (dx) with the dW
    contraction riding the same rotation."""
    _check_operands(x, w, seq_dim, "all_gather_matmul", features_from=1)
    if axis_name is None:
        return jnp.dot(x, w.T)
    if jax.lax.axis_size(axis_name) == 1:
        return jnp.dot(x, w.T)
    return _ag_matmul(x, w, axis_name, seq_dim)


# --- matmul → reduce-scatter --------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _mm_rs(x, w, axis_name, seq_dim):
    tp = jax.lax.axis_size(axis_name)
    s_loc = x.shape[seq_dim] // tp

    def contrib(j):
        return jnp.dot(_seq_chunk(x, seq_dim, j, s_loc), w.T)

    with _ring_span("mm_rs", x, axis_name):
        y, _ = _ring_reduce_scatter(contrib, axis_name)
    return y


def _mm_rs_fwd(x, w, axis_name, seq_dim):
    return _mm_rs(x, w, axis_name, seq_dim), (x, w)


def _mm_rs_bwd(axis_name, seq_dim, res, g):
    x, w = res
    s_loc = g.shape[seq_dim]

    def dw_ride(acc, g_chunk, j):  # g chunks rotate; x slices are local
        return _dw_fold(acc, g_chunk, _seq_chunk(x, seq_dim, j, s_loc))

    with _ring_span("mm_rs_bwd", g, axis_name):
        dx, dw = _ring_all_gather_apply(
            g, lambda c: jnp.dot(c, w), axis_name, seq_dim, acc_fn=dw_ride)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_mm_rs.defvjp(_mm_rs_fwd, _mm_rs_bwd)


def matmul_reduce_scatter(x, w, *, axis_name=mesh_lib.TENSOR_AXIS,
                          seq_dim=0):
    """``psum_scatter(x @ w.T, seq_dim, tiled)`` as the transpose ring —
    the SP ``RowParallelLinear`` epilogue. ``x`` is the full-sequence local
    activation ``(…, in_local)``, ``w`` the ``(out, in_local)`` row shard;
    returns this rank's sequence chunk of the summed product. Backward is
    the all-gather→matmul ring (dx) with dW riding the g rotation."""
    _check_operands(x, w, seq_dim, "matmul_reduce_scatter", features_from=1)
    if axis_name is None:
        return jnp.dot(x, w.T)
    tp = jax.lax.axis_size(axis_name)
    if tp == 1:
        return jnp.dot(x, w.T)
    _check_divisible(x, seq_dim, tp, axis_name, "matmul_reduce_scatter")
    return _mm_rs(x, w, axis_name, seq_dim)


# --- matmul → all-reduce (non-SP RowParallel) ---------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _mm_ar(x, w, axis_name, seq_dim):
    tp = jax.lax.axis_size(axis_name)
    s_loc = x.shape[seq_dim] // tp

    def contrib(j):
        return jnp.dot(_seq_chunk(x, seq_dim, j, s_loc), w.T)

    with _ring_span("mm_ar", x, axis_name):
        chunk, _ = _ring_reduce_scatter(contrib, axis_name)
        # all-gather phase: the reduced chunks rotate back out — pure
        # comm, but each destination chunk was summed once, in ring
        # order, so every rank receives bitwise-identical bytes (an XLA
        # psum makes no such ordering promise)
        y, _ = _ring_all_gather_apply(chunk, lambda c: c, axis_name,
                                      seq_dim)
    return y


def _mm_ar_fwd(x, w, axis_name, seq_dim):
    return _mm_ar(x, w, axis_name, seq_dim), (x, w)


def _mm_ar_bwd(axis_name, seq_dim, res, g):
    # the reduce_from pinned pair (psum forward, identity backward): the
    # cotangent of the reduced output is replicated, so dx and dW are
    # local GEMMs — no collective in this backward, same as blocking
    x, w = res
    dx = jnp.dot(g, w)
    dw = _dw_fold(None, g, x)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_mm_ar.defvjp(_mm_ar_fwd, _mm_ar_bwd)


def matmul_all_reduce(x, w, *, axis_name=mesh_lib.TENSOR_AXIS, seq_dim=0):
    """``psum(x @ w.T)`` as reduce-scatter ring + chunk all-gather — the
    non-SP ``RowParallelLinear`` epilogue. The RS phase overlaps each
    destination chunk's GEMM with the partial sum's hop; the AG phase is
    rotation only. Backward is local (the ``reduce_from`` pinned pair)."""
    _check_operands(x, w, seq_dim, "matmul_all_reduce", features_from=1)
    if axis_name is None:
        return jnp.dot(x, w.T)
    tp = jax.lax.axis_size(axis_name)
    if tp == 1:
        return jnp.dot(x, w.T)
    _check_divisible(x, seq_dim, tp, axis_name, "matmul_all_reduce")
    return _mm_ar(x, w, axis_name, seq_dim)


# --- copy → matmul (non-SP ColumnParallel) ------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _copy_mm(x, w, axis_name, seq_dim):
    return jnp.dot(x, w.T)


def _copy_mm_fwd(x, w, axis_name, seq_dim):
    return jnp.dot(x, w.T), (x, w)


def _copy_mm_bwd(axis_name, seq_dim, res, g):
    # the copy_to pinned pair (identity forward, psum backward): dx must
    # be psum(g @ w) over tp — decomposed so each chunk's GEMM overlaps
    # the ring instead of one blocking GEMM feeding one blocking psum
    x, w = res
    tp = jax.lax.axis_size(axis_name)
    s_loc = g.shape[seq_dim] // tp

    def contrib(j):
        return jnp.dot(_seq_chunk(g, seq_dim, j, s_loc), w)

    with _ring_span("copy_mm_bwd", g, axis_name):
        chunk, _ = _ring_reduce_scatter(contrib, axis_name)
        dx, _ = _ring_all_gather_apply(chunk, lambda c: c, axis_name,
                                       seq_dim)
    dw = _dw_fold(None, g, x)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_copy_mm.defvjp(_copy_mm_fwd, _copy_mm_bwd)


def copy_matmul(x, w, *, axis_name=mesh_lib.TENSOR_AXIS, seq_dim=0):
    """``copy_to(x) @ w.T`` — the non-SP ``ColumnParallelLinear`` pattern.
    Forward is the plain local GEMM (``copy_to`` is the identity);
    backward ring-overlaps the ``psum(g @ w)`` the copy's transpose
    demands. ``x`` must carry the full sequence (it is replicated over
    tp), divisible by the axis size for the backward chunking."""
    _check_operands(x, w, seq_dim, "copy_matmul", features_from=1)
    if axis_name is None:
        return jnp.dot(x, w.T)
    tp = jax.lax.axis_size(axis_name)
    if tp == 1:
        return jnp.dot(x, w.T)
    _check_divisible(x, seq_dim, tp, axis_name, "copy_matmul")
    return _copy_mm(x, w, axis_name, seq_dim)
