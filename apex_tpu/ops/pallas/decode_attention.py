"""Fused decode-attention Pallas kernel: one query token against a KV cache.

The decode hot path is HBM-bound — each generated token must stream the
whole KV cache through the chip once, and arithmetic intensity is O(1)
(one query row per cache row). What a kernel can win here is therefore
not FLOPs but *passes*: the composed XLA formulation materializes the
(heads, max_s) score tensor, writes it, reads it back for the row max,
writes the exp, reads it again for the sum — each a full staging pass
over an O(max_s) tensor ("LLM Inference Acceleration via Efficient
Operation Fusion", arXiv:2502.17728, makes exactly this staging-write
argument for softmax/layernorm on decode). This kernel runs the online-
softmax recurrence in VMEM scratch: the cache streams HBM→VMEM exactly
once and nothing O(max_s) is ever written back.

Layout contract (the attention-native cache layout the inference engine
allocates): q ``(b·h_kv, group, d)`` — the query heads of one kv group
folded into the sublane dim — and k/v ``(b·h_kv, max_s, d)``, a free
reshape of the engine's ``(b, h_kv, max_s, d)`` cache. ``lengths`` rides
the same (rows, 1, LANES) lane carrier as the flash kernels' kv_lens;
KV blocks entirely past a row's length are skipped dynamically (their
DMA still runs — BlockSpec copies are unconditional), so short contexts
in a long cache pay MXU time proportional to the *current* length.

GQA falls out of the layout: the group's q heads share the kv row as
rows of one (group, bk) score block — the head-grouping analog of the
head-batched projection layout (PERF.md). MQA is group == h.

PAGED variant (:func:`decode_attn_paged_fwd`): the serving engine's KV
cache is not one contiguous ``max_s`` strip per sequence but a set of
fixed-size BLOCKS scattered through one shared pool
(``apex_tpu.serving.kv_blocks``), named by a per-slot block table. The
kernel body is IDENTICAL — same online-softmax recurrence, same
dead-row/length masking, same block skip — only the *address* of each kv
block changes: the table rides as a scalar-prefetch operand
(``pltpu.PrefetchScalarGridSpec``) so the BlockSpec index map can read
``table[slot, j]`` on the scalar core while computing the j-th block's
DMA source. Logical column positions (``j*bs + iota``) are unchanged, so
length masking and the in-kernel relative bias work untouched.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.pallas import exact_block
from apex_tpu.ops.pallas.attention import (_LSE_LANES, _REL_LANES, NEG_INF,
                                           _kvlen_rows,
                                           relative_position_bucket)


def _decode_kernel(*refs, scale, bk, nk, rel=None, quant=False):
    """Online-softmax decode step for one (batch, kv-head) row.

    Grid (b·h_kv, nk): the kv axis is the ONLY sequential dim; scratch
    carries (m, l, acc) across kv blocks and the output is written once
    at the last block — no (group, max_s) score tensor exists anywhere,
    in VMEM or HBM.

    ``rel = (num_buckets, max_distance)`` (static) adds the T5 CAUSAL
    bucketed relative bias recomputed in-kernel from a (1, group, 128)
    head-major table block: the query IS position ``kvlen - 1``, so
    rel_pos = col − (kvlen − 1) needs no extra operand beyond the table —
    the decode sibling of the flash kernels' ``rel_bias``.

    ``quant`` (static): the k/v refs hold 1-byte rows and two extra
    (1, 1, bk) fp32 refs carry the per-row scales, so the decode stream
    pays 1-byte bandwidth and fp32 math (the whole point of the
    quantized pool: the kernel is HBM-bound, the bytes are the cost).
    A row's scale is shared by its d cells, so it factors out of both
    contractions: ``q·(k_j s_j) = (q·k_j) s_j`` scales score COLUMN j and
    ``Σ_j p_j (v_j s_j) = Σ_j (p_j s_j) v_j`` scales probability column
    j. The scales therefore stay in the lane orientation they arrive in
    (a (1, bk) row broadcast over the group's sublanes) — dequantizing
    the (bk, d) rows themselves would need the row turned into a (bk, 1)
    column, a lane→sublane relayout Mosaic does not do.
    """
    refs = list(refs)
    q_ref, k_ref, v_ref, len_ref = refs[:4]
    n = 4
    ks_ref = vs_ref = None
    if quant:
        ks_ref, vs_ref = refs[n], refs[n + 1]
        n += 2
    if rel is not None:
        rtab_ref = refs[n]
        n += 1
    o_ref, m_scr, l_scr, acc_scr = refs[n:]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    kvlen = len_ref[0, 0, 0]

    # skip KV blocks entirely past the current length — decode against a
    # pre-allocated max_s cache must cost MXU time ~ the LIVE prefix only
    @pl.when(j * bk < kvlen)
    def _step():
        q = q_ref[0]  # (group, d) — the kv group's query heads
        if quant:
            k = k_ref[0].astype(jnp.float32)
            q = q.astype(jnp.float32)
        else:
            k = k_ref[0]  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (group, bk)
        if quant:
            s = s * ks_ref[0]
        cols = j * bk + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], bk), 1)
        if rel is not None:
            nbk, maxd = rel
            buckets = relative_position_bucket(
                cols - (kvlen - 1), bidirectional=False, num_buckets=nbk,
                max_distance=maxd)  # (group, bk), rows identical
            bias = jnp.zeros(s.shape, jnp.float32)
            for b in range(nbk):
                bias = bias + jnp.where(buckets == b,
                                        rtab_ref[0, :, b:b + 1],
                                        jnp.float32(0.0))
            s = s + bias
        s = jnp.where(cols < kvlen, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if quant:
            acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
                p * vs_ref[0], v_ref[0].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        # length-0 rows never ran a step: l == 0 → zeros out (the flash
        # kernels' dead-row convention)
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)


def _rel_group_operand(table, group, row_map):
    """(spec, arg) for the (h, 128) head-major bias table, one kv group's
    q heads per grid row: rows iterate (batch, kv head), so row r reads
    table rows [(r % h_kv)·group, +group). The table rides as
    (h_kv, group, 128) with (1, group, 128) blocks — Mosaic takes a block
    whose last two dims EQUAL the array's but refuses a (group, 128) block
    over (h, 128) when group is neither a multiple of 8 nor h (MHA's
    group of 1, GQA's 4). ``row_map``: grid -> row r."""
    h_kv = table.shape[0] // group
    return (pl.BlockSpec((1, group, _REL_LANES),
                         lambda *g: (row_map(*g) % h_kv, 0, 0)),
            table.reshape(h_kv, group, _REL_LANES))


def decode_attn_fwd(q, k, v, lengths, *, scale, rel_bias=None, bk=512,
                    interpret=False):
    """q (rows, group, d); k/v (rows, max_s, d) with rows = b·h_kv;
    ``lengths`` (rows,) int32 — positions >= the length are masked and
    whole blocks past it are skipped. Returns (rows, group, d) context.
    Forward-only: decode never differentiates.

    ``rel_bias``: ``(table (h, 128) fp32 head-major, (num_buckets,
    max_distance))`` — causal T5 bucketed bias recomputed in-kernel;
    row r's table block covers its kv group's q heads
    ([(r % h_kv)·group, ...))."""
    rows, group, d = q.shape
    max_s = k.shape[1]
    bk = exact_block(max_s, bk, 128) or max_s
    nk = pl.cdiv(max_s, bk)
    rel, rel_static = (None, None) if rel_bias is None else (
        rel_bias[0], rel_bias[1])

    in_specs = [
        pl.BlockSpec((1, group, d), lambda b, j: (b, 0, 0)),
        pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0)),
        pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0)),
        pl.BlockSpec((1, 1, _LSE_LANES), lambda b, j: (b, 0, 0)),
    ]
    args = [q, k, v, _kvlen_rows(lengths, rows)]
    if rel is not None:
        spec, arg = _rel_group_operand(rel, group, lambda b, j: b)
        in_specs.append(spec)
        args.append(arg)

    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bk=bk, nk=nk,
                          rel=rel_static),
        name="decode_attn",
        grid=(rows, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, group, d), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, group, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*args)


def _paged_kernel(tbl_ref, *refs, scale, bk, nk, rel=None, quant=False):
    """Scalar-prefetch wrapper: the block table is consumed entirely by
    the index maps (it addresses the DMAs); the body never touches it —
    logical positions, masking and bias are exactly the contiguous
    kernel's."""
    del tbl_ref
    _decode_kernel(*refs, scale=scale, bk=bk, nk=nk, rel=rel, quant=quant)


def decode_attn_paged_fwd(q, k_pool, v_pool, lengths, block_tables, *,
                          scale, rel_bias=None, k_scale=None,
                          v_scale=None, interpret=False):
    """Paged decode attention: q ``(rows, group, d)`` with
    ``rows = b·h_kv``; ``k_pool``/``v_pool`` ``(num_blocks·h_kv, bs, d)``
    — the free reshape of the serving pool's ``(num_blocks, h_kv, bs,
    d)`` layout; ``block_tables`` ``(b, nb_max)`` int32 mapping each
    slot's j-th LOGICAL kv block to a pool block id; ``lengths``
    ``(rows,)`` int32 live positions per row. Every table entry must be
    a valid pool index — the engine zero-fills unused entries with the
    reserved dead block 0, whose DMA is harmless (blocks past a row's
    length are compute-skipped, and in-block tails are masked by the
    length like the contiguous kernel). Returns (rows, group, d).

    ``rel_bias`` as in :func:`decode_attn_fwd` (cols are logical
    positions, so the causal bucketed bias is indirection-oblivious).

    ``k_scale``/``v_scale``: the int8-pool path — ``(num_blocks, bs)``
    fp32 per-row scales riding their own scalar-prefetched index maps
    (the SAME table lookup, minus the h_kv fold: scales are shared
    across kv heads and head_dim); the kernel applies them in VMEM, so
    the HBM stream is 1 byte per cell (indirection-oblivious, like the
    bucketed bias).
    """
    rows, group, d = q.shape
    b, nb = block_tables.shape
    h_kv = rows // b
    bs = k_pool.shape[1]
    rel, rel_static = (None, None) if rel_bias is None else (
        rel_bias[0], rel_bias[1])
    quant = k_scale is not None

    # index maps receive the prefetched table LAST; k/v maps translate
    # (row, j) -> pool row table[row // h_kv, j] * h_kv + row % h_kv
    in_specs = [
        pl.BlockSpec((1, group, d), lambda r, j, tbl: (r, 0, 0)),
        pl.BlockSpec((1, bs, d),
                     lambda r, j, tbl, hk=h_kv: (tbl[r // hk, j] * hk
                                                 + r % hk, 0, 0)),
        pl.BlockSpec((1, bs, d),
                     lambda r, j, tbl, hk=h_kv: (tbl[r // hk, j] * hk
                                                 + r % hk, 0, 0)),
        pl.BlockSpec((1, 1, _LSE_LANES), lambda r, j, tbl: (r, 0, 0)),
    ]
    args = [q, k_pool, v_pool, _kvlen_rows(lengths, rows)]
    if quant:
        # (num_blocks, 1, bs) with (1, 1, bs) blocks: the last two block
        # dims must equal the array's (a (1, bs) block over
        # (num_blocks, bs) is refused — second-minor 1 is neither a
        # multiple of 8 nor the full dim)
        scale_spec = pl.BlockSpec(
            (1, 1, bs), lambda r, j, tbl, hk=h_kv: (tbl[r // hk, j], 0, 0))
        in_specs.extend([scale_spec, scale_spec])
        args.extend([k_scale[:, None, :], v_scale[:, None, :]])
    if rel is not None:
        spec, arg = _rel_group_operand(rel, group, lambda r, j, tbl: r)
        in_specs.append(spec)
        args.append(arg)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, group, d), lambda r, j, tbl: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, bk=bs, nk=nb,
                          rel=rel_static, quant=quant),
        name="decode_attn_paged",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), *args)
