"""Blockwise (flash) attention Pallas kernels, forward + backward.

TPU-native replacement for the reference's ``fmhalib``
(``apex/contrib/csrc/fmha/`` — fp16, seq≤512, head_dim 64, SM80 only) and
``fast_multihead_attn`` (``apex/contrib/csrc/multihead_attn/``): one kernel
family with no sequence cap — the online-softmax recurrence streams KV blocks
through VMEM, so sequence length is bounded by HBM, not by a kernel table.

Shapes: q (bh, sq, d), k/v (bh, sk, d) with bh = batch*heads folded. Forward
returns (o, lse) — lse is the softmax log-normalizer row vector that backward
reuses (the same residual the CUTLASS fmha saves). Backward is the standard
two-kernel split: dq accumulates over KV blocks, dk/dv over Q blocks, with
D = rowsum(do·o) precomputed by the caller — except on the seq-major layouts
(packed and bshd) without a bias, where one kernel computes every score tile
once and feeds dq, dk and dv from it (:func:`_bwd_fused_kernel`).

Block sizes default to 1024 (measured best on v5e at seq>=1024 — small
blocks leave the head_dim-64 MXU contraction starved and grid overhead
dominant); d must equal the full head dim
(trailing-dim tiling rule). Causal masking skips whole KV blocks above the
diagonal — the work saving that makes causal flash ~2x dense — and fetches
none of them (:func:`_kv_walk`); a tile wholly under the diagonal runs
without a mask in the forward and the one-pass backward alike
(:func:`_tile_kind`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.pallas import exact_block

NEG_INF = -1e30

# --- in-kernel attention dropout ---------------------------------------------
#
# The reference's fused attention kernels take a dropout probability inside
# the kernel (``apex/contrib/csrc/fmha/fmha_api.cpp:44,80-83``, Philox
# counters; ``apex/contrib/multihead_attn``'s fused softmax-dropout). The
# TPU formulation replaces the stateful Philox stream with a STATELESS
# counter-based hash of the global element coordinates: keep(t, row, col)
# is a pure function of (seed, q-head index, score position), so
# - forward and backward regenerate identical masks with zero saved state
#   (the O(s²) mask tensor never exists — only (bq, bk) blocks in VMEM);
# - the mask is independent of block sizes and of kernel vs XLA dispatch
#   (the XLA fallback evaluates the same function — bit-identical masks);
# - interpret-mode tests cover the real code path (pltpu.prng_random_bits
#   has no interpret lowering in this jax; plain vector ops do).
# Hash: murmur3's 32-bit finalizer (full avalanche) over a per-(seed, t)
# key xor a unique per-element counter — splitmix-style, plenty for
# Bernoulli masks.

_U32 = jnp.uint32


def _fmix32(h):
    """murmur3 fmix32: bijective avalanche mix on uint32."""
    h = h ^ (h >> _U32(16))
    h = h * _U32(0x85EBCA6B)
    h = h ^ (h >> _U32(13))
    h = h * _U32(0xC2B2AE35)
    h = h ^ (h >> _U32(16))
    return h


def dropout_keep(seed, t, rows, cols, rate):
    """Bernoulli(1-rate) keep mask for score elements (rows, cols) of
    q-head ``t``: uniform-in-[0,1) from the hash, compared in the integer
    domain (Mosaic has no uint32->f32 cast). ``seed``/``t`` scalar int32
    (traced ok); ``rows``/``cols`` int32 arrays of GLOBAL score
    coordinates (broadcastable, e.g. (bq, 1) x (1, bk)); ``rate`` static.

    Rows enter through their own fmix pass rather than a ``row·sk + col``
    linear counter: the counter form wraps uint32 when sq·sk > 2^32, which
    would hand row pairs 2^32/sk apart bit-identical masks exactly at the
    long-context scale the kernels advertise (review r4). Per-row key
    material costs one extra fmix32 on a (rows, 1) column — negligible.

    The realized keep probability is ``rate`` quantized to the nearest
    multiple of 2^-24 (the integer-domain compare uses a 24-bit
    threshold); rates below ~3e-8 round to dropout-off (ADVICE r4)."""
    key = _fmix32(seed.astype(_U32) ^ (jnp.asarray(t).astype(_U32)
                                       * _U32(0x9E3779B9)))
    row_key = _fmix32(key ^ rows.astype(_U32))
    thresh = _U32(min(1 << 24, int(round(rate * (1 << 24)))))
    return (_fmix32(row_key ^ cols.astype(_U32)) >> _U32(8)) >= thresh


def _mask_scale(seed, t, i, j, bq, bk, rate, transposed=False):
    """(bq, bk) fp32 dropout multiplier (1/(1-rate) kept, 0 dropped) for
    score block (i, j) — the shared fwd/bwd block recipe. ``transposed``:
    the (bk, bq) multiplier of the block held as Sᵀ — the same hash of the
    same coordinates, rows along lanes."""
    rows = i * bq + jax.lax.broadcasted_iota(
        jnp.int32, (1, bq) if transposed else (bq, 1), int(transposed))
    cols = j * bk + jax.lax.broadcasted_iota(
        jnp.int32, (bk, 1) if transposed else (1, bk), int(not transposed))
    keep = dropout_keep(seed, t, rows, cols, rate)
    return jnp.where(keep, jnp.float32(1.0 / (1.0 - rate)), 0.0)


# --- in-kernel bucketed relative position bias --------------------------------
#
# The T5 relative bias is a LOOKUP: bias(q_pos, k_pos) = table[bucket(k_pos -
# q_pos), head] with bucket() a cheap closed form (exact small offsets, log-
# spaced large ones). Feeding it to the kernels as a materialized (h, sq, sk)
# operand costs O(h·s²) HBM (~1.6 GB fp32 at s=8192, h=6) — defeating the
# fused kernel's entire value proposition (never materializing O(s²)
# tensors; the reference fmha's core design, ``contrib/csrc/fmha``). Instead
# the kernels take the TINY (num_buckets, h) table itself (padded head-major
# to one (1, 128) VMEM row per head) plus a (2,) SMEM global-offset pair, and
# recompute each (bq, bk) bias tile from the grid coordinates: bucket indices
# from the closed form, then a num_buckets-step select-sum against the table
# row (VPU work ~num_buckets ops/element, overlapped with the MXU matmul;
# the arXiv:2502.17728 recompute-beats-streaming argument). The offsets make
# the SAME kernel correct under context parallelism: a shard whose q rows
# start at global position Q and kv block at K computes bucket((K + c) -
# (Q + r)) — bias follows the data onto any sharding for free.

_REL_LANES = 128  # table rows pad to one full lane row; num_buckets <= 128


def _rel_table_operand(table, head_map):
    """(spec, arg) for the (hb, 128) head-major bucket table, one head row
    per grid step. The table rides as (hb, 1, 128) with (1, 1, 128) blocks:
    Mosaic takes a block whose last two dims EQUAL the array's, but refuses
    a (1, 128) block over (hb, 128) — a second-minor block of 1 is neither
    a multiple of 8 nor the full dim. ``head_map``: grid -> head row."""
    return (pl.BlockSpec((1, 1, _REL_LANES),
                         lambda *g: (head_map(*g), 0, 0)),
            table[:, None, :])


def relative_position_bucket(rel_pos, *, bidirectional, num_buckets,
                             max_distance):
    """T5's relative-position bucketing (mesh-tf
    ``_relative_position_bucket``): ``rel_pos = key_pos - query_pos``.
    Half the buckets hold exact small offsets, the other half log-spaced
    larger ones up to ``max_distance``; bidirectional stacks split the
    range by sign, causal stacks clamp the future to bucket 0. Pure jnp on
    any-rank int32 arrays — the SAME function evaluates on (sq, sk) grids
    host-side (materialized oracle) and on (bq, bk) tiles inside the
    Pallas kernels (the sole definition, so kernel and oracle cannot
    drift)."""
    ret = jnp.zeros_like(rel_pos)
    n = -rel_pos
    if bidirectional:
        num_buckets //= 2
        ret = ret + (n < 0).astype(jnp.int32) * num_buckets
        n = jnp.abs(n)
    else:
        n = jnp.maximum(n, 0)
    max_exact = num_buckets // 2
    is_small = n < max_exact
    val_large = max_exact + (
        jnp.log(jnp.maximum(n, 1).astype(jnp.float32) / max_exact)
        / jnp.log(max_distance / max_exact)
        * (num_buckets - max_exact)).astype(jnp.int32)
    val_large = jnp.minimum(val_large, num_buckets - 1)
    return ret + jnp.where(is_small, n, val_large)


def _rel_bias_block(tab_ref, off_ref, i, j, bq, bk, rel):
    """(bq, bk) fp32 bias tile recomputed from grid coordinates: global
    positions from the (2,) SMEM offsets, buckets from the closed form,
    values by a ``num_buckets``-step select-sum over this head's
    (1, 1, 128) table block. ``rel = (num_buckets, bidirectional,
    max_distance)``."""
    nb, bidir, maxd = rel
    rows = off_ref[0] + i * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, 1), 0)
    cols = off_ref[1] + j * bk + jax.lax.broadcasted_iota(
        jnp.int32, (1, bk), 1)
    buckets = relative_position_bucket(
        cols - rows, bidirectional=bidir, num_buckets=nb, max_distance=maxd)
    bias = jnp.zeros((bq, bk), jnp.float32)
    for b in range(nb):
        bias = bias + jnp.where(buckets == b, tab_ref[0, 0, b],
                                jnp.float32(0.0))
    return bias


def _blocks(n, b):
    return pl.cdiv(n, b)


def _fit_block(n, pref):
    """Largest 128-multiple divisor of ``n`` that is <= ``pref``, falling
    back to the whole sequence as one block when n has no 128-multiple
    divisor (the caller's shapes-ok gate rejects such shapes for the
    non-interpret path). Exact tiling matters: Pallas pads partial edge
    blocks with *uninitialized* data, which would flow into the softmax
    accumulators (fwd) and into dk/dv (bwd, padded rows pass the causal
    mask)."""
    return exact_block(n, pref, 128) or n


# --- the band of a windowed causal layer --------------------------------------
#
# ``window`` (static int, causal only): query at global position ``q_pos``
# sees key ``k_pos`` iff ``q_pos - window < k_pos <= q_pos``. A banded call
# walks, for each q block, only the kv blocks its band touches: the grid's
# reduction axis is as long as the widest such run (``_band_walk``), step
# ``jj`` of q block ``i`` is kv block ``_band_first(i) + jj``, and the index
# maps clamp at the band's last block, so a step past it fetches nothing
# (an unchanged block index issues no DMA) and computes nothing. The dkv
# kernel walks the q blocks of each kv block the same way. ``window=None``:
# the forwards and the one-pass backward walk every kv block and, causal, hold
# the q block's last needed one the same way (``_kv_walk``); the split
# backward's maps pass the step through.

def _visible(q_pos, k_pos, window):
    """Whether key ``k_pos`` is visible to query ``q_pos`` (global
    positions, broadcastable int32): causal, and inside the window."""
    keep = k_pos <= q_pos
    if window is not None:
        keep = jnp.logical_and(keep, k_pos > q_pos - window)
    return keep


def _most(a, b):
    """max of two block numbers: Python's on ints (a grid's static length),
    ``jnp.maximum`` on traced ones (a kernel's or an index map's)."""
    return max(a, b) if isinstance(a, int) and isinstance(b, int) else jnp.maximum(a, b)


def _least(a, b):
    return min(a, b) if isinstance(a, int) and isinstance(b, int) else jnp.minimum(a, b)


def _band_first(i, b_own, b_other, shift, reach):
    """First block (of ``b_other`` positions) that the band of block ``i``
    (of ``b_own`` positions) touches: the block holding position
    ``i * b_own + shift - reach``, not below 0. For a q block's kv blocks
    ``shift = off``, ``reach = window - 1``; for a kv block's q blocks
    ``shift = -off``, ``reach = 0``."""
    return _most(i * b_own + shift - reach, 0) // b_other


def _band_last(i, b_own, b_other, shift, reach, n_other):
    """Last block that the band of block ``i`` touches: the one holding
    position ``(i + 1) * b_own - 1 + shift + reach``, not above the last.
    For a q block's kv blocks ``shift = off``, ``reach = 0``; for a kv
    block's q blocks ``shift = -off``, ``reach = window - 1``."""
    return _least(((i + 1) * b_own - 1 + shift + reach) // b_other, n_other - 1)


def _check_window(window, causal, bias, rel_bias):
    if window is None:
        return
    if not causal or window < 1:
        raise ValueError(f"window={window!r} needs causal=True and window >= 1")
    if bias is not None or rel_bias is not None:
        raise ValueError("a windowed call takes no score bias")


def _check_second(second, window, bias, rel_bias, kv_lens, dropout_rate):
    """A second score term rides the plain causal / full call only."""
    if second is None:
        return
    if (window is not None or bias is not None or rel_bias is not None
            or kv_lens is not None or dropout_rate > 0.0):
        raise ValueError("a call with a second score term takes no window, "
                         "score bias, kv_lens or dropout")
    q2, k2 = second
    if q2.ndim != 4 or k2.ndim != 4 or q2.shape[1] % k2.shape[1]:
        raise ValueError(f"second = (q2 (b, h, sq, d2), k2 (b, h2, sk, d2)), "
                         f"h2 | h; got {q2.shape} / {k2.shape}")


def _band_walk(banded, n_own, b_own, b_other, n_other, shift, lo_reach, hi_reach):
    """``(steps, block)`` of a call's reduction axis: how many steps it
    takes and which block of the other axis step ``s`` of own block ``i``
    reads. Unbanded: every block in order. Banded: the widest run any own
    block's band touches (static), counted from the band's first block and
    held at its last."""
    if not banded:
        return n_other, lambda i, s: s
    first = functools.partial(_band_first, b_own=b_own, b_other=b_other, shift=shift,
                              reach=lo_reach)
    last = functools.partial(_band_last, b_own=b_own, b_other=b_other, shift=shift,
                             reach=hi_reach, n_other=n_other)
    steps = max(last(i) - first(i) + 1 for i in range(n_own))
    return steps, lambda i, s: _least(first(i) + s, last(i))


def _shifted(x, off):
    """``x + off``; a zero ``off`` (one sequence length, static) adds no
    operation, so the one-pass backward's program stays the text it was."""
    return x + off if off else x


def _both(a, b):
    """``a and b``: Python's on bools (a count on the host),
    ``jnp.logical_and`` on traced ones (a kernel's)."""
    return (a and b) if isinstance(a, bool) and isinstance(b, bool) else jnp.logical_and(a, b)


def _kv_walk(causal, window, nq, bq, bk, nk, off):
    """``(steps, block)`` of a q block's walk along the kv blocks — the ONE
    rule of the forward wrappers and the one-pass backward. Banded: the
    band's walk (:func:`_band_walk`). Causal: every block in order and HELD
    at the last one the q block needs, the block of its last row's diagonal
    — a step past it is a tile above the diagonal, which computes nothing
    and, its block index unchanged, fetches nothing. Else every block in
    order. Kv lengths are a run-time operand and stay out of the index map:
    a step past a row's length computes nothing and still fetches."""
    if window is not None:
        return _band_walk(True, nq, bq, bk, nk, off, window - 1, 0)
    if not causal:
        return nk, lambda i, j: j

    def last(i):
        block = _shifted((i + 1) * bq - 1, off) // bk
        # with more queries than keys the first q blocks see no key at all
        return _most(block, 0) if off < 0 else block
    return nk, lambda i, j: _least(j, last(i))


def _tile_kind(i, j, bq, bk, off, causal, window):
    """``(run, inner)`` of score tile (q block ``i``, kv block ``j``), from
    block indices alone (Python ints on the host, traced scalars in a kernel
    — the ONE classification of :func:`_fwd_kernel`, :func:`_bwd_fused_kernel`
    and :func:`forward_tiles`). ``run``: the tile holds a visible score, it is
    not above the diagonal (the band ends at the diagonal's block, so a step
    past it is a tile above the diagonal: one test skips both). ``inner``:
    every score of it is visible — the tile's last column at or left of its
    first row's diagonal / its first column inside its last row's window."""
    run = inner = True
    if causal:
        run = j * bk <= _shifted((i + 1) * bq - 1, off)
        inner = (j + 1) * bk - 1 <= _shifted(i * bq, off)
    if window is not None:
        inner = _both(inner, j * bk > _shifted((i + 1) * bq - 1, off) - window)
    return run, inner


def _tile_in_length(run, inner, j, bk, kvlen):
    """:func:`_tile_kind` under a row's kv length (a run-time scalar): a tile
    past it does not run, one it ends in is not fully visible."""
    return _both(run, j * bk < kvlen), _both(inner, (j + 1) * bk <= kvlen)


def forward_tiles(sq, sk, bq, bk, causal, window=None):
    """``(running, fully_visible, skipped)``: the grid steps a head of a
    forward call takes, by what :func:`_fwd_kernel` does at each — static by
    shape, the kernel's three-way branch counted on the host. ``running``
    tiles are computed, ``fully_visible`` of them without a mask (no iota,
    compare or select), the rest crossed by the diagonal or the band's lower
    edge; ``skipped`` steps compute nothing and fetch nothing (above the
    diagonal, or past the band's last block). Kv lengths, a run-time operand,
    are not in it. 8,192 positions in blocks of 1,024: 36, 28, 28; under a
    window of 2,048: 21, 7, 3; 1,024 in blocks of 512: 3, 1, 1."""
    bq, bk = _fit_block(sq, bq), _fit_block(sk, bk)
    nq, nk, off = _blocks(sq, bq), _blocks(sk, bk), sk - sq
    steps, _ = _kv_walk(causal, window, nq, bq, bk, nk, off)
    running = visible = 0
    for i in range(nq):
        first = 0 if window is None else _band_first(i, bq, bk, off, window - 1)
        for j in range(first, min(first + steps, nk)):
            run, inner = _tile_kind(i, j, bq, bk, off, causal, window)
            running += run
            visible += _both(run, inner)
    return running, visible, nq * steps - running


# --- heads of 64, two to a lane tile -------------------------------------------
#
# A folded (b, s, h·d) view takes d-wide column blocks, and 64 lanes are half
# a tile. A packed-layout call whose heads are 64 wide (:func:`packed_pair`)
# therefore takes column blocks of 128 lanes — two adjacent heads, the same
# pair of q, k and v — and the kernels run each tile's mathematics once a head
# of the pair. No lane is sliced: the SMALL operands of a head (q in the
# forward; q, k and dO in the backward — selects over (rows, 128), never over
# a score tile) have the other head's lanes zeroed, so a contraction over all
# 128 lanes yields that head's scores and dP, and ``Pᵀ·dO``, ``dSᵀ·Q``,
# ``dS·K`` land in that head's 64 lanes with zeros beside them: the shared
# (rows, 128) accumulators take both heads by plain addition. A 128 x 128
# MXU spends on a zeroed half what it would leave idle under a contraction
# 64 deep. Everything else of a call is the call at half as many heads of 128.

_PAIR_D, _PAIR_LANES = 64, 128


def packed_pair(h, h_kv, d):
    """Whether a packed-layout call rides pair blocks: heads of 64, an even
    number of them, and ``group == 1`` — a q pair then reads the k and v
    pair at its own index, lane for lane (under a group of q heads the two
    heads of a q pair would share ONE kv head, on the wrong lanes for one
    of them)."""
    return d == _PAIR_D and h == h_kv and h % 2 == 0


def _check_pair(bias):
    if bias is not None:
        raise ValueError("a packed call at heads of 64 takes no score bias "
                         "(the bias kernels read one head a block)")


def _pair_block(s):
    """The block (``bq = bk``) a pair call's BACKWARD prefers, from the
    sequence length alone: half the sequence, between 128 and 1,024. At 1,024
    positions one 1,024-block is ONE tile a head, all of it crossed by the
    diagonal; at 512 three of four tiles run and one of them takes the
    branch with no mask (0.834 ms a layer against 0.904 at 8 x 16 heads;
    256 loses, 1.249: a tile costs about a microsecond whatever its size).
    From 2,048 positions on it is the 1,024 of the other forms. The forward
    keeps 1,024 at every length: measured when its body had no unmasked
    branch to reach, it paid the cost a tile three times over at 512 (0.561
    -> 0.831 ms; PERF.md §6, PR 42)."""
    return max(128, min(1024, s // 2))


def _pair_own(shape, hd):
    """(rows, 128) bool: the lanes of head ``hd`` of a pair."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return lane >= _PAIR_D if hd else lane < _PAIR_D


def _pair_lanes(x, hd):
    """``x`` (rows, 128) with the lanes of the pair's other head zeroed;
    ``hd`` None (no pair): ``x`` itself."""
    if hd is None:
        return x
    return jnp.where(_pair_own(x.shape, hd), x, jnp.zeros_like(x))


def _pair_plane(hd):
    """The index of head ``hd``'s plane of a per-head (2, rows, 1) scratch;
    ``hd`` None (no pair): of the whole (rows, 1) scratch."""
    return slice(None) if hd is None else hd


# --- forward ------------------------------------------------------------------

def _fwd_kernel(*refs, scale, causal, bq, bk, nk, off, varlen, bshd=False,
                rate=0.0, has_bias=False, rel=None, window=None, second=False,
                pair=False):
    """Grid (q-head rows, q blocks, kv steps), the kv steps innermost: the
    online-softmax recurrence over the score tiles of one q block.

    A tile is skipped (above the causal diagonal / past the row's kv
    length), fully visible (no iota, compare or select), or crossed by the
    diagonal, the band's lower edge or the length — branched on block
    indices with ``pl.when`` (:func:`_tile_kind` and, under a length,
    :func:`_tile_in_length`: the one-pass backward's classification with
    this call's ``off``). A skipped step costs the grid
    step alone: the wrappers' kv index maps hold the q block's last needed
    block (:func:`_kv_walk`), so it fetches nothing — except a step past a
    kv LENGTH, a run-time operand no index map can read. A fully visible
    tile costs its two matmuls, the exp and the row reductions; a crossed
    one two (bq, bk) iotas, the compares and a select more. A select whose
    predicate is all true returns its operand, so both branches give a
    fully visible tile the same bits. Not causal and no lengths: one body
    without a mask, no branch. How many steps of a head are which is static
    by shape: :func:`forward_tiles` (8,192 positions in blocks of 1,024:
    36 running, 28 of them fully visible, 28 skipped).

    ``varlen`` is a STATIC specialization flag: without kv lengths the
    kernel carries no length operand, no per-block length select, and no
    dynamic predicate conjunct — the common (non-padded) call pays nothing.
    ``bshd``: the seq-major layout — q/k/v/o ride (b, s, h·d) folded views
    whose blocks are IDENTICAL to the bh-flat ones (a (bq, d) tile, the
    head picked by the block index along the folded feature dim), so only
    the lse carrier's rank differs ((b, h, sq, LANES) vs (bh, sq, LANES)).
    ``rate > 0`` (static) adds in-kernel probs dropout: the softmax
    normalizer ``l`` accumulates UN-dropped p (dropout applies to the
    normalized probabilities), the output accumulator takes the masked,
    1/(1-rate)-scaled p; masks come from :func:`dropout_keep` on global
    coordinates and a seed operand in SMEM.
    ``has_bias`` (static) adds an additive score-bias operand — a
    (1, bq, bk) block of the (hb, sq, sk) bias array, added to the scaled
    scores BEFORE the causal/varlen masks (the reference's in-kernel
    arbitrary mask, ``csrc/megatron/scaled_masked_softmax.cpp:85-94``,
    generalized to any additive bias — T5 relative position bias rides it).
    ``rel`` (static, exclusive with ``has_bias``) instead RECOMPUTES the T5
    bucketed relative bias per tile from a (1, 128) table row + (2,) SMEM
    global offsets (see :func:`_rel_bias_block`) — no O(s²) bias operand
    exists anywhere.
    ``window`` (static): the banded walk of the section above — ``nk`` is
    then the band's run of kv blocks, not the sequence's.
    ``second`` (static): a second score term — two more operands after v,
    a (bq, d2) block of head-major q2 (b, h, sq, d2) and a (bk, d2) block of
    k2 (b, h2, sk, d2), whose product is added to ``q·kᵀ`` before the scale
    (latent attention's rotary part: one key shared by all heads). The value
    block's width is the accumulator's and the output's; it need not be q's.
    ``pair`` (static): the blocks hold two heads of 64, side by side in one
    128-lane tile (the section on pairs above): a tile's mathematics runs
    once a head on q with the other head's lanes zeroed, the running max and
    sum are a plane a head of (2, bq, 1) scratches, each head's 64 lanes of
    ``P·V`` are kept into the shared accumulator, and the lse block holds
    the pair's two rows.
    """
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    n = 3
    if second:
        q2_ref, k2_ref = refs[n:n + 2]
        n += 2
    if has_bias:
        bias_ref = refs[n]
        n += 1
    if rel is not None:
        rtab_ref, roff_ref = refs[n:n + 2]
        n += 2
    if varlen:
        kvlen_ref = refs[n]
        n += 1
    if rate > 0.0:
        seed_ref = refs[n]
        n += 1
    o_ref, lse_ref, m_scr, l_scr, acc_scr = refs[n:]
    t = pl.program_id(0)  # q-head row (dropout mask key)
    i = pl.program_id(1)  # q block
    jj = pl.program_id(2)  # step along the kv blocks
    # k block: the step itself, or (banded) counted from the band's first
    j = jj if window is None else _band_first(i, bq, bk, off, window - 1) + jj

    @pl.when(jj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: process only blocks intersecting the (bottom-right aligned)
    # lower triangle — row r attends cols <= r + off, off = sk - sq; the
    # wrappers' kv index maps hold the last such block (:func:`_kv_walk`), so
    # a skipped step issues no DMA either.
    # varlen: additionally skip KV blocks entirely past this row's valid
    # length (a *dynamic* predicate — pl.when predicates the block; the
    # length is no part of an index map, so THAT block's DMA is issued
    # regardless, only the compute is skipped).
    run, inner = _tile_kind(i, j, bq, bk, off, causal, window)
    if varlen:
        kvlen = kvlen_ref[0, 0, 0]
        run, inner = _tile_in_length(run, inner, j, bk, kvlen)

    heads = (0, 1) if pair else (None,)

    def _head_step(hd, masked):
        # MXU operands stay in the input dtype (bf16 in mixed precision —
        # an fp32 pre-cast would run the matmul at the ~8x-slower fp32 MXU
        # rate); preferred_element_type pins fp32 accumulation either way.
        q = _pair_lanes(q_ref[0], hd)
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if second:
            s = s + jax.lax.dot_general(
                q2_ref[0, 0], k2_ref[0, 0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        s = s * scale  # (bq, bk)
        if has_bias:
            s = s + bias_ref[0].astype(jnp.float32)
        if rel is not None:
            s = s + _rel_bias_block(rtab_ref, roff_ref, i, j, bq, bk, rel)
        if masked:
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            if causal:
                rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                s = jnp.where(_visible(rows + off, cols, window), s, NEG_INF)
            if varlen:
                s = jnp.where(cols < kvlen, s, NEG_INF)
        own = _pair_plane(hd)
        m_prev = m_scr[own]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[own] = l_scr[own] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if rate > 0.0:
            # the dropout key is the q-head row: of a pair, 2·t and 2·t + 1
            pd = p * _mask_scale(seed_ref[0], t if hd is None else 2 * t + hd,
                                 i, j, bq, bk, rate)
        else:
            pd = p
        acc = acc_scr[:] * alpha + jax.lax.dot_general(
            pd.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # of a pair, P·V is this head's in its own 64 lanes only
        acc_scr[:] = acc if hd is None else jnp.where(
            _pair_own(acc.shape, hd), acc, acc_scr[:])
        m_scr[own] = m_new

    def _step(masked):
        for hd in heads:
            _head_step(hd, masked)

    # a select whose predicate is all true returns its operand: the branch
    # with no mask leaves a fully visible tile's scores bit for bit
    if not causal and not varlen:
        _step(False)
    else:
        pl.when(jnp.logical_and(run, inner))(lambda: _step(False))
        pl.when(jnp.logical_and(run, jnp.logical_not(inner)))(lambda: _step(True))

    @pl.when(jj == nk - 1)
    def _finish():
        ls = [jnp.maximum(l_scr[_pair_plane(hd)], 1e-30) for hd in heads]
        l = jnp.where(_pair_own(acc_scr.shape, 0), *ls) if pair else ls[0]
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        for hd, l_hd in zip(heads, ls):
            lse_val = m_scr[_pair_plane(hd)] + jnp.log(l_hd)
            if varlen:
                # fully-masked rows (kvlen == 0): lse would be NEG_INF+log(eps),
                # and backward's exp(s - lse) with s == NEG_INF would overflow
                # to exp(+huge); pin dead rows' lse to 0 so p == exp(NEG_INF).
                lse_val = jnp.where(l_scr[_pair_plane(hd)] > 0.0, lse_val, 0.0)
            # lse rides an (sq, 8) layout: TPU blocks must tile (8, 128) or match
            # the array dim, so a flat (1, bq) row block won't lower — broadcast
            # the column across 8 lanes and let the caller slice lane 0.
            lse_b = jnp.broadcast_to(lse_val, (l_hd.shape[0], _LSE_LANES))
            if bshd:  # (b, h, sq, LANES) carrier; a pair's block holds two heads
                lse_ref[0, hd or 0] = lse_b
            else:
                lse_ref[0] = lse_b


_LSE_LANES = 8


def _expand_rows(x):
    """(bh, sq) -> (bh, sq, 8) broadcast, the tileable carrier layout."""
    return jnp.broadcast_to(x[..., None], (*x.shape, _LSE_LANES))


def _kvlen_rows(kv_lens, bh):
    """(bh,) int32 valid-lengths -> the (bh, 1, 8) lane-carrier the varlen
    kernels read (callers only build this when lengths are present — the
    no-length case compiles kernels with no length operand at all)."""
    return jnp.broadcast_to(kv_lens.astype(jnp.int32)[:, None, None],
                            (bh, 1, _LSE_LANES))



def _group_sum(x, h_kv, group, d, dtype):
    """Per-q-head fp32 dk/dv partials (b, s, h·d) → kv-head grads
    (b, s, h_kv·d): sum each kv group's q heads, THEN cast (fp32 before the
    cross-head sum — the ADVICE r2 precision rule). Used by the dq/dkv
    splits, whose grid rows are q heads: :func:`flash_bwd_bshd` and
    :func:`flash_bwd_packed` with a bias, unequal lengths or a sequence too
    long for the one-pass kernel (which sums the group in VMEM and needs
    none of this).
    On the chip the partials are written and read back: 2 × 0.27 GB a layer
    at 16 heads on 1 × 8,192 positions (PERF.md, PR 25)."""
    b, s, _ = x.shape
    return x.reshape(b, s, h_kv, group, d).sum(3).astype(
        dtype).reshape(b, s, h_kv * d)


def _seed_operand(dropout_seed):
    """(1,) int32 SMEM operand from a scalar seed (traced or host)."""
    if dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    return jnp.asarray(dropout_seed, jnp.int32).reshape(1)


_SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)

# The 512-block cap applies ONLY to the MATERIALIZED (hb, sq, sk) bias
# operand (the oracle/fallback form, and contrib's additive attn_mask): its
# (bq, bk) fp32 blocks are bq·bk·4 bytes double-buffered — 4 MB at 1024²,
# too much VMEM next to the q/k/v/do blocks and accumulators; 1 MB at 512²
# fits. The BUCKETED path carries one (1, 128) table row + a (2,) scalar
# pair instead, so its forward and dq/dkv kernels tile at the normal
# (uncapped) block sizes — the r6 change that removed the cap from the
# production relative-bias path. (Its dtable kernel caps again, for its
# own VMEM reason: see _dtable_blocks.)
_BIAS_BLOCK_CAP = 512


def _bias_blocks(bias, bq, bk):
    """(bq, bk) clamped to the materialized-bias VMEM cap when a bias
    ARRAY operand is present; unchanged otherwise (incl. bucketed)."""
    if bias is not None:
        return min(bq, _BIAS_BLOCK_CAP), min(bk, _BIAS_BLOCK_CAP)
    return bq, bk


def _tail_operands(kv_lens, rows, dropout_rate, dropout_seed, lens_map,
                   bias=None, bias_map=None, bias_block=None,
                   rel=None, rel_map=None):
    """(specs, args) for the OPTIONAL trailing kernel operands, in the
    kernels' fixed unpack order: [score bias] then [rel table + offsets]
    then [kvlen carrier] then [dropout seed]. ``rows`` is the lens
    carrier's leading extent (bh for the flat layout, b for bshd/packed);
    ``lens_map`` the grid->carrier index map; ``bias`` the (hb, sq, sk)
    additive-score array with ``bias_map`` its grid->(row, qblk, kblk) map
    and ``bias_block`` the (1, bq, bk) block shape; ``rel`` the bucketed
    pair (table (hb, 128) fp32 head-major, offsets (2,) int32) with
    ``rel_map`` the grid->head row map. One assembly point so a
    future operand cannot be appended in the wrong order at one of the
    call sites."""
    specs, args = [], []
    if bias is not None:
        specs.append(pl.BlockSpec(bias_block, bias_map))
        args.append(bias)
    if rel is not None:
        spec, arg = _rel_table_operand(rel[0], rel_map)
        specs.append(spec)
        args.append(arg)
        specs.append(_SMEM_SPEC)
        args.append(rel[1])
    if kv_lens is not None:
        specs.append(pl.BlockSpec((1, 1, _LSE_LANES), lens_map))
        args.append(_kvlen_rows(kv_lens, rows))
    if dropout_rate > 0.0:
        specs.append(_SMEM_SPEC)
        args.append(_seed_operand(dropout_seed))
    return specs, args


def flash_fwd(q, k, v, *, scale, causal, kv_lens=None, bias=None,
              rel_bias=None, bq=1024, bk=1024, full_lse=False,
              interpret=False, dropout_rate=0.0, dropout_seed=None):
    """q (bh, sq, d); k/v (bh_kv, sk, d) where bh_kv divides bh — grouped-
    query attention falls out of the kv BlockSpec index maps (q row ``b``
    reads kv row ``b // group``), zero-copy: kv shards are never repeated
    in HBM. ``kv_lens`` (bh,) int32 masks each row's kv positions >= its
    length (padded batches); the MXU/VPU work of KV blocks entirely past
    the length is skipped dynamically (their DMA still runs — a length is
    no part of an index map). ``kv_lens=None`` compiles a kernel with no
    varlen operand or masking at all. Causal: a step above the diagonal is
    skipped and fetches nothing (the k, v and bias maps hold the q block's
    last needed block, :func:`_kv_walk`), a tile wholly under it runs
    without a mask, one the diagonal or a length crosses under the mask
    (:func:`_fwd_kernel`; counted by :func:`forward_tiles`). ``full_lse``
    returns the raw (bh, sq, LANES) lane carrier, which :func:`flash_bwd`
    accepts directly (saves the slice + re-broadcast pair when lse only
    rides residuals).

    ``bias`` (hb, sq, sk) with hb | bh: an additive score bias, row ``r``
    reading bias row ``r % hb`` — (h, sq, sk) shared over batch under the
    b-major row order, (1, sq, sk) fully broadcast, (bh, sq, sk) per-row.
    Added to the scaled scores before masks.

    ``rel_bias`` (exclusive with ``bias``): the BUCKETED relative-bias
    triple ``(table (hb, 128) fp32 head-major, offsets (2,) int32,
    (num_buckets, bidirectional, max_distance))`` — the bias is recomputed
    per tile inside the kernel from the tiny table (see
    :func:`_rel_bias_block`); no (hb, sq, sk) array exists anywhere. Row
    ``r`` reads table row ``r % hb`` (same contract as ``bias``)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    group = bh // k.shape[0]
    bq, bk = _bias_blocks(bias, bq, bk)
    bq, bk = _fit_block(sq, bq), _fit_block(sk, bk)
    nq, nk = _blocks(sq, bq), _blocks(sk, bk)
    varlen = kv_lens is not None
    hb = 0 if bias is None else bias.shape[0]
    rel, rel_static = (None, None) if rel_bias is None else (
        rel_bias[:2], rel_bias[2])
    rhb = 0 if rel is None else rel[0].shape[0]

    _, kv_block = _kv_walk(causal, None, nq, bq, bk, nk, sk - sq)

    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j, g=group: (b // g, kv_block(i, j), 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j, g=group: (b // g, kv_block(i, j), 0)),
    ]
    args = [q, k, v]
    tail_specs, tail_args = _tail_operands(
        kv_lens, bh, dropout_rate, dropout_seed, lambda b, i, j: (b, 0, 0),
        bias, lambda b, i, j, hb=hb: (b % hb, i, kv_block(i, j)), (1, bq, bk),
        rel, lambda b, i, j, rhb=rhb: b % rhb)
    in_specs += tail_specs
    args += tail_args

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, off=sk - sq, varlen=varlen,
                          rate=dropout_rate, has_bias=bias is not None,
                          rel=rel_static),
        name="flash_fwd",
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, _LSE_LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, _LSE_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*args)
    return o, (lse if full_lse else lse[..., 0])


def flash_fwd_packed(qkv, h, h_kv, d, *, scale, causal, kv_lens=None,
                     bias=None, bq=1024, bk=1024, full_lse=False,
                     interpret=False, dropout_rate=0.0, dropout_seed=None):
    """Flash forward reading q/k/v directly out of the PACKED projection
    output: ``qkv`` (b, s, (h+2·h_kv)·d), features ordered q|k|v with heads
    contiguous inside each part. The same buffer rides in three times with
    window-offset index maps — the projection GEMM's output feeds the
    kernel with no slice, no copy, no layout change at all. Returns
    (o (b, s, h·d), lse (b, h, s)) — or, with ``full_lse``, the raw
    (b, h, s, LANES) lane carrier the kernel wrote, which
    :func:`flash_bwd_packed` accepts directly: round-tripping through the
    sliced form costs a slice + re-broadcast pair per layer for nothing.

    Causal, a grid step is a tile skipped (above the diagonal: nothing
    computed and, the k and v windows held at the q block's last needed
    block by :func:`_kv_walk`, nothing fetched), fully visible (no mask) or
    crossed by the diagonal or a length (the mask): :func:`_fwd_kernel`,
    counted by :func:`forward_tiles` — at 8,192 positions 36 of a head's 64
    steps run, 28 of them without a mask.

    ``bias`` (hb, s, s) with hb | h: additive score bias, q-head row
    ``t = b·h + h_i`` reading bias row ``t % hb`` (i.e. per-head bias
    shared over batch at hb == h; broadcast at hb == 1).

    Heads of 64 (:func:`packed_pair`: an even number of them, ``h == h_kv``)
    ride two to a 128-lane block: the call is ``flash_fwd_packed_pair`` and
    takes no ``bias``. Lengths and dropout ride it; the results have the same
    shapes."""
    b, s, _ = qkv.shape
    pair = packed_pair(h, h_kv, d)
    if pair:
        # the call at half as many heads of 128 (the section on pairs above)
        _check_pair(bias)
        h, h_kv, d = h // 2, h_kv // 2, _PAIR_LANES
    per = 2 if pair else 1               # heads a block
    group = h // h_kv
    bq, bk = _bias_blocks(bias, bq, bk)
    bq, bk = _fit_block(s, bq), _fit_block(s, bk)
    nq, nk = _blocks(s, bq), _blocks(s, bk)
    varlen = kv_lens is not None
    hb = 0 if bias is None else bias.shape[0]

    _, kv_block = _kv_walk(causal, None, nq, bq, bk, nk, 0)

    args = [qkv, qkv, qkv]
    in_specs = [
        pl.BlockSpec((1, bq, d),
                     lambda t, i, j, h=h: (t // h, i, t % h)),
        pl.BlockSpec((1, bk, d),
                     lambda t, i, j, h=h, g=group:
                     (t // h, kv_block(i, j), h + (t % h) // g)),
        pl.BlockSpec((1, bk, d),
                     lambda t, i, j, h=h, hk=h_kv, g=group:
                     (t // h, kv_block(i, j), h + hk + (t % h) // g)),
    ]
    tail_specs, tail_args = _tail_operands(
        kv_lens, b, dropout_rate, dropout_seed,
        lambda t, i, j, h=h: (t // h, 0, 0),
        bias, lambda t, i, j, hb=hb: (t % hb, i, kv_block(i, j)), (1, bq, bk))
    in_specs += tail_specs
    args += tail_args

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, off=0, varlen=varlen,
                          bshd=True, rate=dropout_rate,
                          has_bias=bias is not None, pair=pair),
        name="flash_fwd_packed_pair" if pair else "flash_fwd_packed",
        grid=(b * h, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d),
                         lambda t, i, j, h=h: (t // h, i, t % h)),
            pl.BlockSpec((1, per, bq, _LSE_LANES),
                         lambda t, i, j, h=h: (t // h, t % h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h * d), qkv.dtype),
            jax.ShapeDtypeStruct((b, per * h, s, _LSE_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, bq, 1) if pair else (bq, 1), jnp.float32),
            pltpu.VMEM((2, bq, 1) if pair else (bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*args)
    return o, (lse if full_lse else lse[..., 0])


# VMEM a kernel of this package may ask for: Mosaic's default scoped limit on
# a v5e is 16 MiB, the chip has 128 MiB; 100 leaves the compiler its own.
_VMEM_FLOOR, _VMEM_CAP = 16 * 2 ** 20, 100 * 2 ** 20


def _vmem_limit(nbytes):
    """``vmem_limit_bytes`` for a kernel that holds ``nbytes`` resident."""
    return min(_VMEM_CAP, max(_VMEM_FLOOR, nbytes))


def _fused_bwd_vmem_bytes(s, d, bq, bk, itemsize, dv=None, d2=0, heads=1):
    """VMEM the one-pass packed backward holds at once: the two whole-
    sequence fp32 dk/dv accumulators, their (double-buffered) output blocks
    at the kv dtype, the q/do/o/k/v/dq blocks, and the score-tile
    temporaries of one step (S, P, dP, dS in fp32, their MXU-dtype copies,
    a dropout multiplier: under eight (bq, bk) fp32 tiles). ``dv``: the
    value head's width where it is not ``d``; ``d2``: the second score
    term's, whose q2/k2/dq2 blocks, dk2 accumulator and output ride at
    whole 128-lane tiles. ``heads``: the heads a block holds (2 in a pair
    call), each with a step's tile temporaries of its own."""
    dv = d if dv is None else dv
    w = d + dv + -(-d2 // 128) * 128             # lanes resident per position
    accumulators = s * w * 4
    outputs = 2 * s * w * itemsize
    blocks = (2 * (2 * bq + bk) * w * itemsize
              + bq * (w - dv) * 4)
    tiles = heads * 8 * bq * bk * 4
    return accumulators + outputs + blocks + tiles


def _split_bwd_vmem_limit(d, bq, bk, itemsize, out_itemsize):
    """``vmem_limit_bytes`` of a split (dq | dkv) backward call, or ``None``
    (Mosaic's default) where the call fits the default: the double-buffered
    q/do and k/v blocks, the output blocks and their fp32 accumulators, and
    the score-tile temporaries of one step. Heads of 256 at 1,024-blocks
    ask 17 MiB where heads of 128 and less stay inside the 16."""
    blocks = 2 * (2 * bq + 2 * bk) * d * itemsize
    outputs = 3 * 2 * max(bq, bk) * d * max(out_itemsize, 4)
    need = blocks + outputs + 8 * bq * bk * 4
    return _vmem_limit(need) if d > 128 else None


def _bwd_fused_kernel(*refs, scale, causal, bq, bk, nq, nk, group, h, h_kv,
                      varlen, rate=0.0, window=None, second=None, pair=False):
    """One-pass backward of the seq-major layouts (the packed q|k|v buffer
    and separate bshd arrays: the two differ in index maps only): grid
    (b·h_kv, group, nq, nk), kv blocks innermost. Every (q block, kv block)
    score tile is computed ONCE and feeds dq, dk and dv — five matmuls a
    tile where the dq/dkv split pays seven and runs the mask/exp chain
    twice.

    The tile is held TRANSPOSED, Sᵀ = K·Qᵀ (bk, bq): dV += Pᵀ·dO and
    dK += dSᵀ·Q are then plain (bk, bq)·(bq, d) products with the big tile
    as the streamed left operand, and only dQ contracts over the tile's
    rows. Row statistics ride as (1, bq) lane rows: ``lse`` comes in that
    form, D = rowsum(dO∘O) is computed here when a q block is first visited
    (its first kv step) — no XLA prologue, no carrier.

    Accumulators are fp32 VMEM scratch and each gradient leaves once: dq in
    a (bq, d) scratch over the kv blocks of one visit; dk/dv in whole-
    sequence (s, d) scratches that stay resident across the group's q heads
    and all q blocks and are cast and written once a kv head, at kv width —
    the cross-head sum happens in fp32 by construction (ADVICE r2), and no
    per-q-head partial ever reaches HBM. ``scale`` multiplies dq and dk once
    at their write instead of every dS tile.

    A tile is skipped (above the causal diagonal / past the row's kv
    length, as in the split), fully visible (no iota, compare or select),
    or crossed by the diagonal, the band's lower edge or the length —
    branched on block indices with ``pl.when`` (:func:`_tile_kind`, the
    forward's too). ``window`` (static): the kv
    axis is the band's run of blocks (``nk`` steps, counted from the band's
    first block; the section on the band above), the mask is
    :func:`_visible`. Dropout regenerates the forward's mask: the same hash
    on the same global coordinates, ``t`` the q-head row of the forward
    grid.

    ``second`` (static; the number of q heads that share one head of k2): a
    second score term ``q2·k2ᵀ`` (see :func:`_fwd_kernel`) — two more
    operands after lse, two more gradients (dq2 a q block, dk2 a whole
    sequence) and their two fp32 scratches. dk2 is summed in VMEM like dk,
    over ALL the q heads of its k2 head: they are consecutive rows of the
    grid's first axis, which such a call therefore walks in order
    (``"arbitrary"``), and the accumulator is zeroed at the first and
    written at the last of them.

    ``pair`` (static): the blocks hold two heads of 64 side by side (the
    section on pairs above; ``h``, ``h_kv`` count pairs). A tile runs once a
    head on q, k and dO with the other head's lanes zeroed, so every product
    is that head's own and the (rows, 128) accumulators take both heads by
    addition; lse comes as the pair's two lane rows and D is a plane a head
    of a (2, 1, bq) scratch."""
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref = refs[:6]
    n = 6
    if second:
        q2_ref, k2_ref = refs[n:n + 2]
        n += 2
    if varlen:
        kvlen_ref = refs[n]
        n += 1
    if rate > 0.0:
        seed_ref = refs[n]
        n += 1
    if second:
        (dq_ref, dk_ref, dv_ref, dq2_ref, dk2_ref,
         dq_scr, dk_scr, dv_scr, delta_scr, dq2_scr, dk2_scr) = refs[n:]
    else:
        dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, delta_scr = refs[n:]
    r = pl.program_id(0)  # (batch, kv head) row
    g = pl.program_id(1)  # q head within the kv group
    i = pl.program_id(2)  # q block
    jj = pl.program_id(3)  # step along the kv blocks (inner, dq accumulated)
    # kv block: the step itself, or (banded) counted from the band's first
    j = jj if window is None else _band_first(i, bq, bk, 0, window - 1) + jj
    first = jnp.logical_and(jnp.logical_and(g == 0, i == 0), jj == 0)
    last = jnp.logical_and(jnp.logical_and(g == group - 1, i == nq - 1),
                           jj == nk - 1)

    @pl.when(first)
    def _init_kv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(jj == 0)
    def _visit():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        prod = do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32)
        if pair:  # a head's D from its own 64 of the transposed 128 rows
            prod_t = prod.T
            delta_scr[0] = jnp.sum(prod_t[:_PAIR_D], axis=0, keepdims=True)
            delta_scr[1] = jnp.sum(prod_t[_PAIR_D:], axis=0, keepdims=True)
        else:
            delta_scr[...] = jnp.sum(prod.T, axis=0, keepdims=True)  # (1, bq)

    if second:
        # this q head's place among the q heads of its k2 head
        place = ((r % h_kv) * group + g) % second
        ends = jnp.logical_and(i == nq - 1, jj == nk - 1)

        @pl.when(jnp.logical_and(place == 0, jnp.logical_and(i == 0, jj == 0)))
        def _init_k2():
            dk2_scr[...] = jnp.zeros_like(dk2_scr)

        @pl.when(jj == 0)
        def _visit2():
            dq2_scr[...] = jnp.zeros_like(dq2_scr)

    run, inner = _tile_kind(i, j, bq, bk, 0, causal, window)
    if varlen:
        kvlen = kvlen_ref[0, 0, 0]
        run, inner = _tile_in_length(run, inner, j, bk, kvlen)

    def _tile(masked, hd=None):
        # bf16 MXU operands, fp32 accumulation (see _fwd_kernel)
        q = _pair_lanes(q_ref[0], hd)
        k = _pair_lanes(k_ref[0], hd)
        v = v_ref[0]
        do = _pair_lanes(do_ref[0], hd)
        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if second:
            q2 = q2_ref[0, 0]
            k2 = k2_ref[0, 0]
            st = st + jax.lax.dot_general(
                k2, q2, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        st = st * scale  # (bk, bq) = Sᵀ
        if masked:
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
            if causal:
                rows = i * bq + jax.lax.broadcasted_iota(
                    jnp.int32, (1, bq), 1)
                st = jnp.where(_visible(rows, cols, window), st, NEG_INF)
            if varlen:
                st = jnp.where(cols < kvlen, st, NEG_INF)
        pt = jnp.exp(st - lse_ref[0, hd or 0])
        if rate > 0.0:
            t = (r // h_kv) * h + (r % h_kv) * group + g  # the forward's row
            if hd is not None:
                t = 2 * t + hd
            ms = _mask_scale(seed_ref[0], t, i, j, bq, bk, rate,
                             transposed=True)
            pd = pt * ms  # dropped+rescaled probs: dV = Pdᵀ dO
        else:
            pd = pt
        kv_rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
        dv_scr[kv_rows, :] += jax.lax.dot_general(
            pd.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if rate > 0.0:
            dpt = dpt * ms
        delta = delta_scr[...] if hd is None else delta_scr[hd]
        dst = (pt * (dpt - delta)).astype(q.dtype)  # dSᵀ / scale
        dk_scr[kv_rows, :] += jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_scr[...] += jax.lax.dot_general(
            dst, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if second:
            dk2_scr[kv_rows, :] += jax.lax.dot_general(
                dst, q2, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dq2_scr[...] += jax.lax.dot_general(
                dst, k2, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    def _tiles(masked):
        for hd in (0, 1) if pair else (None,):
            _tile(masked, hd)

    if not causal and not varlen:
        _tiles(False)
    else:
        pl.when(jnp.logical_and(run, inner))(lambda: _tiles(False))
        pl.when(jnp.logical_and(run, jnp.logical_not(inner)))(
            lambda: _tiles(True))

    @pl.when(jj == nk - 1)
    def _write_dq():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)

    @pl.when(last)
    def _write_dkv():
        def block(blk, carry):
            rows = pl.ds(pl.multiple_of(blk * bk, bk), bk)
            dk_ref[0, rows, :] = (dk_scr[rows, :] * scale).astype(dk_ref.dtype)
            dv_ref[0, rows, :] = dv_scr[rows, :].astype(dv_ref.dtype)
            return carry
        jax.lax.fori_loop(0, dk_scr.shape[0] // bk, block, 0)

    if second:
        @pl.when(jj == nk - 1)
        def _write_dq2():
            dq2_ref[0, 0] = (dq2_scr[...] * scale).astype(dq2_ref.dtype)

        @pl.when(jnp.logical_and(place == second - 1, ends))
        def _write_dk2():
            def block(blk, carry):
                rows = pl.ds(pl.multiple_of(blk * bk, bk), bk)
                dk2_ref[0, 0, rows, :] = (dk2_scr[rows, :] * scale).astype(
                    dk2_ref.dtype)
                return carry
            jax.lax.fori_loop(0, dk2_scr.shape[0] // bk, block, 0)


def _fused_bwd_fits(bias, rel_bias, sq, sk, d, bq, bk, itemsize, dv=None, d2=0, heads=1):
    """The rule that picks the one-pass backward, read from the operands:
    no score bias of either kind (the dbias / dtable kernels take D as an
    operand), one sequence length (``sq == sk``: the tile walk assumes the
    diagonal starts at the origin), and accumulators, blocks and tile
    temporaries inside the VMEM a kernel may ask for."""
    return (bias is None and rel_bias is None and sq == sk
            and _fused_bwd_vmem_bytes(sq, d, bq, bk, itemsize, dv, d2, heads) <= _VMEM_CAP)


def packed_pair_fits(s, itemsize):
    """Whether a pair call (:func:`packed_pair`) of ``s`` positions has its
    backward: the one-pass kernel only, so the (s, 128) accumulators of a kv
    pair must pass :func:`_fused_bwd_fits` at the blocks the call takes."""
    blk = _fit_block(s, _pair_block(s))
    return _fused_bwd_fits(None, None, s, s, _PAIR_LANES, blk, blk, itemsize, heads=2)


def bshd_two_width_fits(sq, sk, d, dv, d2, itemsize):
    """Whether the seq-major kernels take a call whose value head is ``dv``
    wide beside q/k heads of ``d``, with a second score term of width ``d2``
    (0: none): such a call has the one-pass backward only, so its shapes
    must pass :func:`_fused_bwd_fits` at the blocks the call takes."""
    return _fused_bwd_fits(None, None, sq, sk, d, _fit_block(sq, 1024), _fit_block(sk, 1024),
                           itemsize, dv, d2)


def _flash_bwd_fused(q3, k3, v3, o3, lse, do3, *, h, h_kv, d, packed, scale,
                     causal, kv_lens, bq, bk, interpret, dropout_rate,
                     dropout_seed, window=None, second=None):
    """Launch :func:`_bwd_fused_kernel` over folded (b, s, heads·d) operands;
    (dq (b, s, h·d), dk, dv (b, s, h_kv·d)) in the operands' dtypes.
    ``packed``: the three operands are one q|k|v buffer, whose k and v
    windows start at head columns ``h`` and ``h + h_kv``; separate bshd
    arrays start at column 0. Nothing else tells the layouts apart.
    ``second = (q2 (b, h, s, d2), k2 (b, h2, s, d2))``, head-major: the second
    score term; the call is then ``flash_bwd_bshd_mla_fused`` and returns
    dq2 and dk2 in those shapes after dv. v, o and do may be narrower or
    wider than q and k (``dv`` from v3's width). A packed buffer of heads of
    64 (:func:`packed_pair`) is walked as half as many heads of 128, two lse
    rows a block: ``flash_bwd_packed_pair_fused``."""
    b, s, _ = q3.shape
    pair = packed and packed_pair(h, h_kv, d)
    if pair:
        h, h_kv, d = h // 2, h_kv // 2, _PAIR_LANES
    per = 2 if pair else 1               # heads a block
    group = h // h_kv
    dv = d if packed else v3.shape[-1] // h_kv
    d2 = 0 if second is None else second[0].shape[-1]
    share = None if second is None else h // second[1].shape[1]
    k_col, v_col = (h, h + h_kv) if packed else (0, 0)
    nq, nk = _blocks(s, bq), _blocks(s, bk)
    # (b, h, 1, s) lane rows: the transposed tile broadcasts lse along its
    # sublanes (one strided slice of the forward's carrier a layer)
    lse_rows = (lse[..., 0] if lse.ndim == 4 else lse)[:, :, None, :]

    def head(r, g):  # q-head index within the batch row
        return (r % h_kv) * group + g

    steps, kv_block = _kv_walk(causal, window, nq, bq, bk, nk, 0)

    qm = lambda r, g, i, j: (r // h_kv, i, head(r, g))  # noqa: E731
    km = lambda r, g, i, j: (r // h_kv, kv_block(i, j), k_col + r % h_kv)  # noqa: E731
    vm = lambda r, g, i, j: (  # noqa: E731
        r // h_kv, kv_block(i, j), v_col + r % h_kv)
    dkm = lambda r, g, i, j: (r // h_kv, 0, r % h_kv)  # noqa: E731
    in_specs = [pl.BlockSpec((1, bq, d), qm),
                pl.BlockSpec((1, bk, d), km),
                pl.BlockSpec((1, bk, dv), vm),
                pl.BlockSpec((1, bq, dv), qm),
                pl.BlockSpec((1, bq, dv), qm),
                pl.BlockSpec((1, per, 1, bq),
                             lambda r, g, i, j: (r // h_kv, head(r, g), 0, i))]
    out_specs = [pl.BlockSpec((1, bq, d), qm),
                 pl.BlockSpec((1, s, d), dkm),
                 pl.BlockSpec((1, s, dv), dkm)]
    out_shape = [jax.ShapeDtypeStruct((b, s, h * d), q3.dtype),
                 jax.ShapeDtypeStruct((b, s, h_kv * d), k3.dtype),
                 jax.ShapeDtypeStruct((b, s, h_kv * dv), v3.dtype)]
    scratch_shapes = [pltpu.VMEM((bq, d), jnp.float32),
                      pltpu.VMEM((s, d), jnp.float32),
                      pltpu.VMEM((s, dv), jnp.float32),
                      pltpu.VMEM((2, 1, bq) if pair else (1, bq), jnp.float32)]
    # dk/dv accumulate across the group, the q blocks and the kv blocks of
    # one (batch, kv head) row: all three stay sequential
    semantics = ("parallel", "arbitrary", "arbitrary", "arbitrary")
    if second is not None:
        q2m = lambda r, g, i, j: (r // h_kv, head(r, g), i, 0)  # noqa: E731
        k2m = lambda r, g, i, j: (  # noqa: E731
            r // h_kv, head(r, g) // share, kv_block(i, j), 0)
        dk2m = lambda r, g, i, j: (r // h_kv, head(r, g) // share, 0, 0)  # noqa: E731
        in_specs += [pl.BlockSpec((1, 1, bq, d2), q2m),
                     pl.BlockSpec((1, 1, bk, d2), k2m)]
        out_specs += [pl.BlockSpec((1, 1, bq, d2), q2m),
                      pl.BlockSpec((1, 1, s, d2), dk2m)]
        out_shape += [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in second]
        scratch_shapes += [pltpu.VMEM((bq, d2), jnp.float32),
                           pltpu.VMEM((s, d2), jnp.float32)]
        # dk2 accumulates across the kv heads of a batch row too
        semantics = ("arbitrary",) * 4
    tail_specs, tail_args = _tail_operands(
        kv_lens, b, dropout_rate, dropout_seed,
        lambda r, g, i, j: (r // h_kv, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale,
                          causal=causal, bq=bq, bk=bk, nq=nq, nk=steps,
                          group=group, h=h, h_kv=h_kv,
                          varlen=kv_lens is not None, rate=dropout_rate,
                          window=window, second=share, pair=pair),
        name="flash_bwd_packed_pair_fused" if pair else (
            "flash_bwd_packed_fused" if packed else (
                "flash_bwd_bshd_mla_fused" if second is not None else (
                    "flash_bwd_bshd_fused" if window is None
                    else "flash_bwd_bshd_win_fused"))),
        grid=(b * h_kv, group, nq, steps),
        in_specs=in_specs + tail_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=_vmem_limit(_fused_bwd_vmem_bytes(
                s, d, bq, bk, q3.dtype.itemsize, dv, d2, heads=per))),
        interpret=interpret,
    )(q3, k3, v3, do3, o3, lse_rows, *(second or ()), *tail_args)


def flash_bwd_packed(qkv, h, h_kv, d, o, lse, do, *, scale, causal,
                     kv_lens=None, bias=None, bq=None, bk=None,
                     interpret=False, dropout_rate=0.0, dropout_seed=None):
    """Backward of :func:`flash_fwd_packed`: returns SEPARATE folded grads
    (dq (b, s, h·d), dk/dv (b, s, h_kv·d)) — the caller contracts each
    against its weight window (plain 2D GEMMs), never materializing a
    packed dqkv.

    Unbiased attention takes ONE kernel, ``flash_bwd_packed_fused``, at any
    number of blocks (see :func:`_bwd_fused_kernel`): each score tile
    computed once, dk/dv summed over the kv group in VMEM and written at kv
    width. What picks it is what the shapes show (:func:`_fused_bwd_fits`):
    no bias, and the two whole-sequence fp32 accumulators fit the VMEM a
    kernel may ask for (:func:`_fused_bwd_vmem_bytes`; at d = 128 up to
    ~32 k positions). A
    longer sequence, or a bias (the dbias kernel takes D as an operand),
    rides the dq/dkv split: ``flash_bwd_packed_dq`` / ``_dkv`` with per-q-
    head fp32 dk/dv partials and :func:`_group_sum`, then
    ``flash_bwd_dbias``.

    ``lse`` may be the sliced (b, h, s) form or the (b, h, s, LANES)
    carrier exactly as :func:`flash_fwd_packed` ``full_lse=True`` returned
    it.

    ``bias`` (hb, s, s), hb | h: adds a fourth output dbias (hb, s, s)
    fp32 (see :func:`flash_bwd`).

    Heads of 64 in pairs (:func:`packed_pair`) take the one kernel too, as
    ``flash_bwd_packed_pair_fused``; they have no split, so no ``bias`` and
    no sequence past :func:`packed_pair_fits`. ``bq`` / ``bk`` None: 1,024,
    of a pair call :func:`_pair_block` of the sequence length."""
    b, s, _ = qkv.shape
    group = h // h_kv
    pair = packed_pair(h, h_kv, d)
    if pair:  # the one-pass kernel only
        _check_pair(bias)
    bq, bk = (blk or (_pair_block(s) if pair else 1024) for blk in (bq, bk))
    bq, bk = _bias_blocks(bias, bq, bk)
    bq, bk = _fit_block(s, bq), _fit_block(s, bk)
    nq, nk = _blocks(s, bq), _blocks(s, bk)
    varlen = kv_lens is not None
    hb = 0 if bias is None else bias.shape[0]

    if pair and not packed_pair_fits(s, qkv.dtype.itemsize):
        raise NotImplementedError(
            f"heads of 64 in pairs have the one-pass backward only, and {s} "
            f"positions do not fit it (packed_pair_fits)")
    if pair or _fused_bwd_fits(bias, None, s, s, d, bq, bk, qkv.dtype.itemsize):
        return _flash_bwd_fused(
            qkv, qkv, qkv, o, lse, do, h=h, h_kv=h_kv, d=d, packed=True,
            scale=scale, causal=causal, kv_lens=kv_lens, bq=bq, bk=bk,
            interpret=interpret, dropout_rate=dropout_rate,
            dropout_seed=dropout_seed)
    lse4 = lse if lse.ndim == 4 else _expand_rows(lse)
    delta = jnp.sum(
        do.astype(jnp.float32).reshape(b, s, h, d)
        * o.astype(jnp.float32).reshape(b, s, h, d), axis=-1)
    delta4 = _expand_rows(delta.transpose(0, 2, 1))
    qm = lambda t, i, j, h=h: (t // h, i, t % h)  # noqa: E731
    km = lambda t, i, j, h=h, g=group: (t // h, j, h + (t % h) // g)  # noqa: E731
    vm = lambda t, i, j, h=h, hk=h_kv, g=group: (  # noqa: E731
        t // h, j, h + hk + (t % h) // g)
    dom = lambda t, i, j, h=h: (t // h, i, t % h)  # noqa: E731
    rm = lambda t, i, j, h=h: (t // h, t % h, i, 0)  # noqa: E731
    extra_specs, extra_args = _tail_operands(
        kv_lens, b, dropout_rate, dropout_seed,
        lambda t, i, j, h=h: (t // h, 0, 0),
        bias, lambda t, i, j, hb=hb: (t % hb, i, j), (1, bq, bk))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, off=0, varlen=varlen,
                          bshd=True, rate=dropout_rate,
                          has_bias=bias is not None),
        name="flash_bwd_packed_dq",
        grid=(b * h, nq, nk),
        in_specs=[pl.BlockSpec((1, bq, d), qm),
                  pl.BlockSpec((1, bk, d), km),
                  pl.BlockSpec((1, bk, d), vm),
                  pl.BlockSpec((1, bq, d), dom),
                  pl.BlockSpec((1, 1, bq, _LSE_LANES), rm),
                  pl.BlockSpec((1, 1, bq, _LSE_LANES), rm)] + extra_specs,
        out_specs=pl.BlockSpec((1, bq, d), qm),
        out_shape=jax.ShapeDtypeStruct((b, s, h * d), qkv.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qkv, qkv, qkv, do, lse4, delta4, *extra_args)

    qm2 = lambda t, j, i, h=h: (t // h, i, t % h)  # noqa: E731
    km2 = lambda t, j, i, h=h, g=group: (t // h, j, h + (t % h) // g)  # noqa: E731
    vm2 = lambda t, j, i, h=h, hk=h_kv, g=group: (  # noqa: E731
        t // h, j, h + hk + (t % h) // g)
    dom2 = lambda t, j, i, h=h: (t // h, i, t % h)  # noqa: E731
    rm2 = lambda t, j, i, h=h: (t // h, t % h, i, 0)  # noqa: E731
    dkm = lambda t, j, i, h=h: (t // h, j, t % h)  # noqa: E731
    dkv_dt = jnp.float32 if group > 1 else qkv.dtype
    extra_specs2, _ = _tail_operands(
        kv_lens, b, dropout_rate, dropout_seed,
        lambda t, j, i, h=h: (t // h, 0, 0),
        bias, lambda t, j, i, hb=hb: (t % hb, i, j), (1, bq, bk))

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, off=0, varlen=varlen,
                          bshd=True, rate=dropout_rate,
                          has_bias=bias is not None),
        name="flash_bwd_packed_dkv",
        grid=(b * h, nk, nq),
        in_specs=[pl.BlockSpec((1, bq, d), qm2),
                  pl.BlockSpec((1, bk, d), km2),
                  pl.BlockSpec((1, bk, d), vm2),
                  pl.BlockSpec((1, bq, d), dom2),
                  pl.BlockSpec((1, 1, bq, _LSE_LANES), rm2),
                  pl.BlockSpec((1, 1, bq, _LSE_LANES), rm2)] + extra_specs2,
        out_specs=[pl.BlockSpec((1, bk, d), dkm),
                   pl.BlockSpec((1, bk, d), dkm)],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h * d), dkv_dt),
            jax.ShapeDtypeStruct((b, s, h * d), dkv_dt),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qkv, qkv, qkv, do, lse4, delta4, *extra_args)
    if group > 1:
        dk = _group_sum(dk, h_kv, group, d, qkv.dtype)
        dv = _group_sum(dv, h_kv, group, d, qkv.dtype)
    if bias is None:
        return dq, dk, dv
    # dbias over the packed buffer: q/k/v windows picked by feature-block
    # offsets (0 | h | h+h_kv), row r = bi·hb + th (see flash_bwd_bshd)
    nb = (b * h) // hb
    qmap = lambda th, i, j, bi, hb=hb, h=h: (  # noqa: E731
        (bi * hb + th) // h, i, (bi * hb + th) % h)
    kmap = lambda th, i, j, bi, hb=hb, h=h, g=group: (  # noqa: E731
        (bi * hb + th) // h, j, h + ((bi * hb + th) % h) // g)
    vmap = lambda th, i, j, bi, hb=hb, h=h, hk=h_kv, g=group: (  # noqa: E731
        (bi * hb + th) // h, j, h + hk + ((bi * hb + th) % h) // g)
    rmap = lambda th, i, j, bi, hb=hb, h=h: (  # noqa: E731
        (bi * hb + th) // h, (bi * hb + th) % h, i, 0)
    db_specs = [
        pl.BlockSpec((1, bq, d), qmap),
        pl.BlockSpec((1, bk, d), kmap),
        pl.BlockSpec((1, bk, d), vmap),
        pl.BlockSpec((1, bq, d), qmap),
        pl.BlockSpec((1, 1, bq, _LSE_LANES), rmap),
        pl.BlockSpec((1, 1, bq, _LSE_LANES), rmap),
        pl.BlockSpec((1, bq, bk), lambda th, i, j, bi: (th, i, j)),
    ]
    db_args = [qkv, qkv, qkv, do, lse4, delta4, bias]
    if varlen:
        db_specs.append(pl.BlockSpec(
            (1, 1, _LSE_LANES),
            lambda th, i, j, bi, hb=hb, h=h: ((bi * hb + th) // h, 0, 0)))
        db_args.append(_kvlen_rows(kv_lens, b))
    if dropout_rate > 0.0:
        db_specs.append(_SMEM_SPEC)
        db_args.append(_seed_operand(dropout_seed))
    dbias = _dbias_pallas(
        db_args, db_specs, hb=hb, sq=s, sk=s, nq=nq, nk=nk, nb=nb,
        bq=bq, bk=bk, scale=scale, causal=causal, off=0,
        varlen=varlen, bshd=True, rate=dropout_rate, interpret=interpret)
    return dq, dk, dv, dbias


def flash_fwd_bshd(q, k, v, *, scale, causal, kv_lens=None, bias=None,
                   rel_bias=None, bq=1024, bk=1024, full_lse=False,
                   interpret=False, dropout_rate=0.0, dropout_seed=None,
                   window=None, second=None):
    """Seq-major flash forward: q (b, sq, h, d); k (b, sk, h_kv, d); v
    (b, sk, h_kv, dv), whose width is the output's and need not be d.

    The (s, h·d)-minor layout is exactly what the QKV projection GEMMs
    emit, so no layout conversion feeds the kernel (removes the
    ~4.5 GB/step of pre/post-kernel copies the bh-flat layout cost the
    flagship, PERF.md r3). Mechanics: the operands ride as (b, s, h·d)
    folded views (free bitcasts) and the head is selected by the block
    index along the folded feature dim — a d-wide column block, satisfying
    Mosaic's (8, 128) trailing-tile rule where a 4D singleton-head block
    cannot. Returns (o (b, sq, h, d), lse (b, h, sq)).

    ``kv_lens`` (b,) int32: per-BATCH valid kv lengths (heads share a
    row's length — the padded-batch case); same masking/skip semantics as
    :func:`flash_fwd`.

    Causal, a grid step is a tile skipped (above the diagonal or past the
    band: nothing computed and, the k, v, k2 and bias maps held at the q
    block's last needed block by :func:`_kv_walk`, nothing fetched), fully
    visible (no mask) or crossed by the diagonal, the band's lower edge or
    a length (the mask): :func:`_fwd_kernel`, counted by
    :func:`forward_tiles`.

    ``bias`` (hb, sq, sk) with hb | h: additive score bias, q-head row
    ``t = b·h + h_i`` reading bias row ``t % hb``. ``rel_bias``: the
    bucketed triple (see :func:`flash_fwd`), table row ``t % hb``.

    ``window`` (static int; causal, no bias): a query sees its last
    ``window`` keys, itself among them. The call is then named
    ``flash_fwd_bshd_win`` and walks each q block's band only.

    ``second = (q2 (b, h, sq, d2), k2 (b, h2, sk, d2))``, HEAD-major, h2 | h:
    a second score term, ``(q·kᵀ + q2·k2ᵀ) * scale`` — latent attention's
    rotary part, whose one key serves every head and is read by index map,
    never repeated in HBM. Head-major because d2 is narrower than a lane
    tile (64): a (bq, d2) block of a (s, d2) plane is legal where a d2-wide
    column block of a folded (s, h·d2) view is not. The call is then named
    ``flash_fwd_bshd_mla`` (no window, bias, lengths or dropout with it)."""
    b, sq, h, d = q.shape
    sk, h_kv = k.shape[1], k.shape[2]
    dv = v.shape[3]
    group = h // h_kv
    bq, bk = _bias_blocks(bias, bq, bk)
    bq, bk = _fit_block(sq, bq), _fit_block(sk, bk)
    nq, nk = _blocks(sq, bq), _blocks(sk, bk)
    off = sk - sq
    _check_window(window, causal, bias, rel_bias)
    _check_second(second, window, bias, rel_bias, kv_lens, dropout_rate)
    steps, kv_block = _kv_walk(causal, window, nq, bq, bk, nk, off)
    varlen = kv_lens is not None
    hb = 0 if bias is None else bias.shape[0]
    rel, rel_static = (None, None) if rel_bias is None else (
        rel_bias[:2], rel_bias[2])
    rhb = 0 if rel is None else rel[0].shape[0]

    args = [q.reshape(b, sq, h * d), k.reshape(b, sk, h_kv * d),
            v.reshape(b, sk, h_kv * dv)]
    in_specs = [
        pl.BlockSpec((1, bq, d),
                     lambda t, i, j, h=h: (t // h, i, t % h)),
        pl.BlockSpec((1, bk, d),
                     lambda t, i, j, h=h, g=group:
                     (t // h, kv_block(i, j), (t % h) // g)),
        pl.BlockSpec((1, bk, dv),
                     lambda t, i, j, h=h, g=group:
                     (t // h, kv_block(i, j), (t % h) // g)),
    ]
    if second is not None:
        d2, share = second[0].shape[-1], h // second[1].shape[1]
        args += list(second)
        in_specs += [
            pl.BlockSpec((1, 1, bq, d2),
                         lambda t, i, j, h=h: (t // h, t % h, i, 0)),
            pl.BlockSpec((1, 1, bk, d2),
                         lambda t, i, j, h=h, g=share:
                         (t // h, (t % h) // g, kv_block(i, j), 0)),
        ]
    tail_specs, tail_args = _tail_operands(
        kv_lens, b, dropout_rate, dropout_seed,
        lambda t, i, j, h=h: (t // h, 0, 0),
        bias, lambda t, i, j, hb=hb: (t % hb, i, kv_block(i, j)), (1, bq, bk),
        rel, lambda t, i, j, rhb=rhb: t % rhb)
    in_specs += tail_specs
    args += tail_args

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=steps, off=off, varlen=varlen,
                          bshd=True, rate=dropout_rate,
                          has_bias=bias is not None, rel=rel_static,
                          window=window, second=second is not None),
        name="flash_fwd_bshd_mla" if second is not None else (
            "flash_fwd_bshd" if window is None else "flash_fwd_bshd_win"),
        grid=(b * h, nq, steps),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, dv),
                         lambda t, i, j, h=h: (t // h, i, t % h)),
            pl.BlockSpec((1, 1, bq, _LSE_LANES),
                         lambda t, i, j, h=h: (t // h, t % h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, h * dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, _LSE_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*args)
    return o.reshape(b, sq, h, dv), (lse if full_lse else lse[..., 0])


# --- backward -----------------------------------------------------------------

def _rd_row(ref, bshd):
    """lse/delta carrier block → (rows, LANES): the bshd carrier is the
    4D (b, h, sq, LANES) array, the flat one (bh, sq, LANES)."""
    return ref[0, 0] if bshd else ref[0]


def _bwd_dq_kernel(*refs, scale, causal, bq, bk, nk, off, varlen,
                   bshd=False, rate=0.0, has_bias=False, rel=None,
                   window=None):
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    n = 6
    if has_bias:
        bias_ref = refs[n]
        n += 1
    if rel is not None:
        rtab_ref, roff_ref = refs[n:n + 2]
        n += 2
    if varlen:
        kvlen_ref = refs[n]
        n += 1
    if rate > 0.0:
        seed_ref = refs[n]
        n += 1
    dq_ref, acc_scr = refs[n:]
    t = pl.program_id(0)
    i = pl.program_id(1)
    jj = pl.program_id(2)
    # banded: the step counts kv blocks from the band's first (_fwd_kernel)
    j = jj if window is None else _band_first(i, bq, bk, off, window - 1) + jj

    @pl.when(jj == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = (not causal) or (j * bk <= (i + 1) * bq - 1 + off)
    if varlen:
        kvlen = kvlen_ref[0, 0, 0]
        run = jnp.logical_and(run, j * bk < kvlen)

    @pl.when(run)
    def _step():
        # bf16 MXU operands, fp32 accumulation (see _fwd_kernel)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if has_bias:
            s = s + bias_ref[0].astype(jnp.float32)
        if rel is not None:
            s = s + _rel_bias_block(rtab_ref, roff_ref, i, j, bq, bk, rel)
        if causal or varlen:
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if causal:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            s = jnp.where(_visible(rows + off, cols, window), s, NEG_INF)
        if varlen:
            s = jnp.where(cols < kvlen, s, NEG_INF)
        p = jnp.exp(s - _rd_row(lse_ref, bshd)[:, 0:1])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if rate > 0.0:
            # dS = P ∘ (M/(1-r) ∘ dPd − Δ): the mask re-enters on the dPd
            # term only (Δ already equals rowsum(Pd ∘ dPd) — see the
            # softmax-dropout chain in flash_bwd's docstring)
            dp = dp * _mask_scale(seed_ref[0], t, i, j, bq, bk, rate)
        ds = (p * (dp - _rd_row(delta_ref, bshd)[:, 0:1]) * scale
              ).astype(k.dtype)
        acc_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(jj == nk - 1)
    def _finish():
        dq_ref[0] = acc_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, bq, bk, nq, off, varlen,
                    bshd=False, rate=0.0, has_bias=False, rel=None,
                    window=None, nq_all=None):
    """``window`` (static): the grid's inner axis is the run of q blocks
    whose band touches kv block ``j`` (``nq`` steps of the ``nq_all`` q
    blocks), counted from the first of them."""
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    n = 6
    if has_bias:
        bias_ref = refs[n]
        n += 1
    if rel is not None:
        rtab_ref, roff_ref = refs[n:n + 2]
        n += 2
    if varlen:
        kvlen_ref = refs[n]
        n += 1
    if rate > 0.0:
        seed_ref = refs[n]
        n += 1
    dk_ref, dv_ref, dk_scr, dv_scr = refs[n:]
    t = pl.program_id(0)
    j = pl.program_id(1)  # k block (outer)
    ii = pl.program_id(2)  # step along the q blocks (inner, accumulated)
    i = ii if window is None else _band_first(j, bk, bq, -off, 0) + ii

    @pl.when(ii == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = (not causal) or ((i + 1) * bq - 1 + off >= j * bk)
    if window is not None:
        # a step past the band's last q block (the index maps held its block)
        run = i <= _band_last(j, bk, bq, -off, window - 1, nq_all)
    if varlen:
        kvlen = kvlen_ref[0, 0, 0]
        run = jnp.logical_and(run, j * bk < kvlen)

    @pl.when(run)
    def _step():
        # bf16 MXU operands, fp32 accumulation (see _fwd_kernel)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if has_bias:
            s = s + bias_ref[0].astype(jnp.float32)
        if rel is not None:
            s = s + _rel_bias_block(rtab_ref, roff_ref, i, j, bq, bk, rel)
        if causal or varlen:
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if causal:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            s = jnp.where(_visible(rows + off, cols, window), s, NEG_INF)
        if varlen:
            s = jnp.where(cols < kvlen, s, NEG_INF)
        p = jnp.exp(s - _rd_row(lse_ref, bshd)[:, 0:1])  # (bq, bk)
        if rate > 0.0:
            ms = _mask_scale(seed_ref[0], t, i, j, bq, bk, rate)
            pd = p * ms  # dropped+rescaled probs: dV = Pdᵀ dO
        else:
            pd = p
        dv_scr[:] += jax.lax.dot_general(
            pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if rate > 0.0:
            dp = dp * ms
        ds = (p * (dp - _rd_row(delta_ref, bshd)[:, 0:1]) * scale
              ).astype(q.dtype)
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ii == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dbias_kernel(*refs, scale, causal, bq, bk, nb, hb, off, varlen,
                      bshd=False, rate=0.0):
    """dbias[th] = Σ_b dS over the rows sharing bias row ``th`` (bias grad
    = sum of dS over batch — the custom-VJP contract for the additive
    score bias). dS = P ∘ (M/(1-r)∘dPd − Δ) recomputed blockwise, exactly
    the dq/dkv kernels' recipe, UNscaled (the 1/√d scale belongs to dq/dk,
    not to the bias which enters S additively).

    Grid (hb, nq, nk, nb) with the BATCH dim innermost: TPU Pallas only
    accumulates an output block over *consecutive* grid steps, and the
    cross-batch reduction is the one the dq/dkv grids (batch outermost)
    cannot host — hence a third kernel. Costs one extra QKᵀ + dO·Vᵀ pair
    (~2 of backward's 7 GEMMs), paid only when a bias is present.

    Row identity: global q-head row r = b·hb + th — the same ``t`` the
    forward grid used, so the dropout mask hash regenerates bit-exactly."""
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref = refs[:7]
    n = 7
    if varlen:
        kvlen_ref = refs[n]
        n += 1
    if rate > 0.0:
        seed_ref = refs[n]
        n += 1
    dbias_ref, acc_scr = refs[n:]
    th = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    b = pl.program_id(3)
    r = b * hb + th  # global q-head row (the forward grid's t)

    @pl.when(b == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = (not causal) or (j * bk <= (i + 1) * bq - 1 + off)
    if varlen:
        kvlen = kvlen_ref[0, 0, 0]
        run = jnp.logical_and(run, j * bk < kvlen)

    @pl.when(run)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale + bias_ref[0].astype(jnp.float32)
        if causal or varlen:
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if causal:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            s = jnp.where(cols <= rows + off, s, NEG_INF)
        if varlen:
            s = jnp.where(cols < kvlen, s, NEG_INF)
        p = jnp.exp(s - _rd_row(lse_ref, bshd)[:, 0:1])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if rate > 0.0:
            dp = dp * _mask_scale(seed_ref[0], r, i, j, bq, bk, rate)
        acc_scr[:] += p * (dp - _rd_row(delta_ref, bshd)[:, 0:1])

    @pl.when(b == nb - 1)
    def _finish():
        dbias_ref[0] = acc_scr[:]


def _dbias_pallas(args, in_specs, *, hb, sq, sk, nq, nk, nb, bq, bk, scale,
                  causal, off, varlen, bshd, rate, interpret):
    """Launch :func:`_bwd_dbias_kernel` — shared by the three layouts
    (only ``in_specs``/``args`` differ). Returns (hb, sq, sk) fp32."""
    return pl.pallas_call(
        functools.partial(_bwd_dbias_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nb=nb, hb=hb, off=off,
                          varlen=varlen, bshd=bshd, rate=rate),
        name="flash_bwd_dbias",
        grid=(hb, nq, nk, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, bk),
                               lambda th, i, j, b: (th, i, j)),
        out_shape=jax.ShapeDtypeStruct((hb, sq, sk), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, bk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # the b accumulation is order-dependent: innermost dim stays
            # sequential ("arbitrary"), the block-indexed dims parallel
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(*args)


def _bwd_dtable_kernel(*refs, scale, causal, bq, bk, nq, nk, nb, hb, off,
                       varlen, bshd=False, rate=0.0, rel=None):
    """Bucket-table gradient for the IN-KERNEL relative bias:
    dtable[bucket, th] = Σ over the rows sharing table column ``th`` and
    over all (r, c) with bucket(c − r) == bucket of the UNSCALED dS —
    the chain rule of the per-tile recompute, with the (sq, sk) → bucket
    contraction done inside the kernel (dS itself never leaves VMEM; the
    O(s²) dbias intermediate of the materialized path has no analog here).

    Grid (hb, nq, nk, nb), ALL inner dims accumulating into one (1, 128)
    output row per table column — unlike the dbias kernel (whose (hb, sq,
    sk) output blocks are indexed by (i, j), forcing batch-innermost),
    nothing here depends on (i, j), so the whole inner grid is one long
    consecutive revisit of the same block. Per-step cost: the dq/dkv
    kernels' dS recompute + ``num_buckets`` masked reductions of the
    (bq, bk) tile (VPU, overlapped with the step's two GEMMs).

    Row identity: global q-head row r = b·hb + th (the forward grid's
    ``t``), so the dropout mask hash regenerates bit-exactly."""
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    n = 6
    rtab_ref, roff_ref = refs[n:n + 2]
    n += 2
    if varlen:
        kvlen_ref = refs[n]
        n += 1
    if rate > 0.0:
        seed_ref = refs[n]
        n += 1
    dtab_ref, acc_scr = refs[n:]
    th = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    b = pl.program_id(3)
    r = b * hb + th  # global q-head row (the forward grid's t)
    nbk, bidir, maxd = rel

    @pl.when(jnp.logical_and(jnp.logical_and(i == 0, j == 0), b == 0))
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = (not causal) or (j * bk <= (i + 1) * bq - 1 + off)
    if varlen:
        kvlen = kvlen_ref[0, 0, 0]
        run = jnp.logical_and(run, j * bk < kvlen)

    @pl.when(run)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        s = s + _rel_bias_block(rtab_ref, roff_ref, i, j, bq, bk, rel)
        if causal or varlen:
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if causal:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            s = jnp.where(cols <= rows + off, s, NEG_INF)
        if varlen:
            s = jnp.where(cols < kvlen, s, NEG_INF)
        p = jnp.exp(s - _rd_row(lse_ref, bshd)[:, 0:1])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if rate > 0.0:
            dp = dp * _mask_scale(seed_ref[0], r, i, j, bq, bk, rate)
        ds = p * (dp - _rd_row(delta_ref, bshd)[:, 0:1])
        # bucket indices of this tile, recomputed exactly as forward
        grows = roff_ref[0] + i * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, 1), 0)
        gcols = roff_ref[1] + j * bk + jax.lax.broadcasted_iota(
            jnp.int32, (1, bk), 1)
        buckets = relative_position_bucket(
            gcols - grows, bidirectional=bidir, num_buckets=nbk,
            max_distance=maxd)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, _REL_LANES), 1)
        upd = jnp.zeros((1, _REL_LANES), jnp.float32)
        for bkt in range(nbk):
            sb = jnp.sum(jnp.where(buckets == bkt, ds, 0.0))
            upd = upd + jnp.where(lane == bkt, sb, jnp.float32(0.0))
        acc_scr[:] += upd

    @pl.when(jnp.logical_and(jnp.logical_and(i == nq - 1, j == nk - 1),
                             b == nb - 1))
    def _finish():
        dtab_ref[0] = acc_scr[:]


def _dtable_blocks(sq, sk, bq, bk):
    """(bq, bk, nq, nk) for the dtable kernel's own grid. It holds the
    score, probability, dP, dS and bucket tiles of one (bq, bk) step live
    across the ``num_buckets`` masked reductions — at 1024² that is 41 MB
    of scoped VMEM against Mosaic's 16 MB default on a v5e ("Scoped
    allocation with size 41.14M and limit 16.00M exceeded"), so it tiles
    at the materialized-bias cap whatever the dq/dkv kernels chose."""
    bq = _fit_block(sq, min(bq, _BIAS_BLOCK_CAP))
    bk = _fit_block(sk, min(bk, _BIAS_BLOCK_CAP))
    return bq, bk, _blocks(sq, bq), _blocks(sk, bk)


def _dtable_pallas(args, in_specs, *, hb, nq, nk, nb, bq, bk, scale,
                   causal, off, varlen, bshd, rate, rel, interpret):
    """Launch :func:`_bwd_dtable_kernel` — shared by the flat and bshd
    layouts (only ``in_specs``/``args`` differ). Returns (hb, 128) fp32
    head-major bucket-table grads (caller slices/transposes back to the
    (num_buckets, hb) table shape)."""
    # (hb, 1, 128) output rows for the same reason as _rel_table_operand
    return pl.pallas_call(
        functools.partial(_bwd_dtable_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, nk=nk, nb=nb, hb=hb,
                          off=off, varlen=varlen, bshd=bshd, rate=rate,
                          rel=rel),
        name="flash_bwd_dtable",
        grid=(hb, nq, nk, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, _REL_LANES),
                               lambda th, i, j, b: (th, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((hb, 1, _REL_LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, _REL_LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # every inner dim accumulates into the one output row, so the
            # whole inner grid must stay sequential
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
    )(*args)[:, 0]


def flash_bwd(q, k, v, o, lse, do, *, scale, causal, kv_lens=None,
              bias=None, rel_bias=None, bq=1024, bk=1024, interpret=False,
              dropout_rate=0.0, dropout_seed=None):
    """Gradients; with grouped kv (bh_kv < bh) dk/dv come back at kv shape —
    the dkv kernel runs per *q*-head (its scratch accumulates over q blocks
    within one grid row, so cross-head accumulation can't live in-kernel)
    and the per-head partials are summed over each kv group outside, where
    XLA fuses the reduction into the kernel's output write.

    ``lse`` is the sliced (bh, sq) form or the (bh, sq, LANES) carrier from
    ``flash_fwd(full_lse=True)``.

    ``bias`` (hb, sq, sk), hb | bh (row r reads bias row r % hb — see
    :func:`flash_fwd`): returns a FOURTH output, dbias (hb, sq, sk) fp32 =
    Σ over the rows sharing each bias row of the unscaled dS, produced by
    :func:`_bwd_dbias_kernel` (batch-innermost grid).

    ``rel_bias`` (the bucketed triple, see :func:`flash_fwd`): the dq/dkv
    kernels recompute the bias per tile, and the FOURTH output is the
    head-major bucket-table grad (hb, 128) fp32 from
    :func:`_bwd_dtable_kernel` — no O(s²) dbias intermediate exists."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    group = bh // k.shape[0]
    bq, bk = _bias_blocks(bias, bq, bk)
    bq, bk = _fit_block(sq, bq), _fit_block(sk, bk)
    nq, nk = _blocks(sq, bq), _blocks(sk, bk)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse3 = lse if lse.ndim == 3 else _expand_rows(lse)
    delta3 = _expand_rows(delta)
    varlen = kv_lens is not None
    hb = 0 if bias is None else bias.shape[0]
    rel, rel_static = (None, None) if rel_bias is None else (
        rel_bias[:2], rel_bias[2])
    rhb = 0 if rel is None else rel[0].shape[0]
    _, extra_args = _tail_operands(
        kv_lens, bh, dropout_rate, dropout_seed, None, bias, None, None,
        rel, None)

    def tail_specs(index_map, bias_map, rel_map):
        specs, _ = _tail_operands(
            kv_lens, bh, dropout_rate, dropout_seed, index_map,
            bias, bias_map, (1, bq, bk), rel, rel_map)
        return specs

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, off=sk - sq, varlen=varlen,
                          rate=dropout_rate, has_bias=bias is not None,
                          rel=rel_static),
        name="flash_bwd_dq",
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, _LSE_LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, _LSE_LANES), lambda b, i, j: (b, i, 0)),
        ] + tail_specs(lambda b, i, j: (b, 0, 0),
                       lambda b, i, j, hb=hb: (b % hb, i, j),
                       lambda b, i, j, rhb=rhb: b % rhb),
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v, do, lse3, delta3, *extra_args)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, off=sk - sq, varlen=varlen,
                          rate=dropout_rate, has_bias=bias is not None,
                          rel=rel_static),
        name="flash_bwd_dkv",
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, _LSE_LANES), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, _LSE_LANES), lambda b, j, i: (b, i, 0)),
        ] + tail_specs(lambda b, j, i: (b, 0, 0),
                       lambda b, j, i, hb=hb: (b % hb, i, j),
                       lambda b, j, i, rhb=rhb: b % rhb),
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            # grouped kv: per-q-head partials stay fp32 so the group-sum
            # below accumulates unrounded (a bf16 partial would round each
            # head's contribution before the sum); ungrouped writes go
            # straight out in the kv dtype
            jax.ShapeDtypeStruct(
                (bh, sk, d), jnp.float32 if group > 1 else k.dtype),
            jax.ShapeDtypeStruct(
                (bh, sk, d), jnp.float32 if group > 1 else v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v, do, lse3, delta3, *extra_args)
    if group > 1:
        dk = dk.reshape(-1, group, sk, d).sum(1).astype(k.dtype)
        dv = dv.reshape(-1, group, sk, d).sum(1).astype(v.dtype)
    if rel is not None:
        nb = bh // rhb
        bq, bk, nq, nk = _dtable_blocks(sq, sk, bq, bk)
        qmap = lambda th, i, j, b, rhb=rhb: (b * rhb + th, i, 0)  # noqa: E731
        kmap = lambda th, i, j, b, rhb=rhb, g=group: (  # noqa: E731
            (b * rhb + th) // g, j, 0)
        tab_spec, tab_arg = _rel_table_operand(
            rel[0], lambda th, i, j, b: th)
        dt_specs = [
            pl.BlockSpec((1, bq, d), qmap),
            pl.BlockSpec((1, bk, d), kmap),
            pl.BlockSpec((1, bk, d), kmap),
            pl.BlockSpec((1, bq, d), qmap),
            pl.BlockSpec((1, bq, _LSE_LANES), qmap),
            pl.BlockSpec((1, bq, _LSE_LANES), qmap),
            tab_spec,
            _SMEM_SPEC,
        ]
        dt_args = [q, k, v, do, lse3, delta3, tab_arg, rel[1]]
        if varlen:
            dt_specs.append(pl.BlockSpec(
                (1, 1, _LSE_LANES),
                lambda th, i, j, b, rhb=rhb: (b * rhb + th, 0, 0)))
            dt_args.append(_kvlen_rows(kv_lens, bh))
        if dropout_rate > 0.0:
            dt_specs.append(_SMEM_SPEC)
            dt_args.append(_seed_operand(dropout_seed))
        dtable = _dtable_pallas(
            dt_args, dt_specs, hb=rhb, nq=nq, nk=nk, nb=nb, bq=bq, bk=bk,
            scale=scale, causal=causal, off=sk - sq, varlen=varlen,
            bshd=False, rate=dropout_rate, rel=rel_static,
            interpret=interpret)
        return dq, dk, dv, dtable
    if bias is None:
        return dq, dk, dv
    nb = bh // hb
    qmap = lambda th, i, j, b, hb=hb: (b * hb + th, i, 0)  # noqa: E731
    kmap = lambda th, i, j, b, hb=hb, g=group: (  # noqa: E731
        (b * hb + th) // g, j, 0)
    db_specs = [
        pl.BlockSpec((1, bq, d), qmap),
        pl.BlockSpec((1, bk, d), kmap),
        pl.BlockSpec((1, bk, d), kmap),
        pl.BlockSpec((1, bq, d), qmap),
        pl.BlockSpec((1, bq, _LSE_LANES), qmap),
        pl.BlockSpec((1, bq, _LSE_LANES), qmap),
        pl.BlockSpec((1, bq, bk), lambda th, i, j, b: (th, i, j)),
    ]
    db_args = [q, k, v, do, lse3, delta3, bias]
    if varlen:
        db_specs.append(pl.BlockSpec(
            (1, 1, _LSE_LANES),
            lambda th, i, j, b, hb=hb: (b * hb + th, 0, 0)))
        db_args.append(_kvlen_rows(kv_lens, bh))
    if dropout_rate > 0.0:
        db_specs.append(_SMEM_SPEC)
        db_args.append(_seed_operand(dropout_seed))
    dbias = _dbias_pallas(
        db_args, db_specs, hb=hb, sq=sq, sk=sk, nq=nq, nk=nk, nb=nb,
        bq=bq, bk=bk, scale=scale, causal=causal, off=sk - sq,
        varlen=varlen, bshd=False, rate=dropout_rate, interpret=interpret)
    return dq, dk, dv, dbias


def flash_bwd_bshd(q, k, v, o, lse, do, *, scale, causal, kv_lens=None,
                   bias=None, rel_bias=None, bq=1024, bk=1024,
                   interpret=False, dropout_rate=0.0, dropout_seed=None,
                   window=None, second=None):
    """Seq-major backward (cf. :func:`flash_fwd_bshd`): q/o/do
    (b, sq, h, d), k/v (b, sk, h_kv, d), lse (b, h, sq) or the
    (b, h, sq, LANES) carrier from ``flash_fwd_bshd(full_lse=True)``.
    Returns (dq (b, sq, h, d), dk/dv (b, sk, h_kv, d)); with ``bias``
    (hb, sq, sk), hb | h, a fourth output dbias (hb, sq, sk) fp32 (see
    :func:`flash_bwd`); with ``rel_bias`` (the bucketed triple) a fourth
    output dtable (hb, 128) fp32 head-major (see :func:`flash_bwd`).

    Unbiased self-length attention takes ONE kernel, ``flash_bwd_bshd_fused``
    (with ``window``: ``flash_bwd_bshd_win_fused``, its kv axis the band's
    run of blocks) — the packed layout's one-pass kernel over three arrays
    (:func:`_bwd_fused_kernel`), picked by the same rule from what the
    operands show (:func:`_fused_bwd_fits`): no ``bias``, no ``rel_bias``,
    ``sq == sk``, and the two (s, d) fp32 dk/dv accumulators with the blocks
    and tile temporaries inside the VMEM a kernel may ask for (52 MiB of the
    100 at d = 128, s = 8,192; 71 at d = 256). A bias of either kind,
    ``sq != sk`` (cross attention, a longer key sequence) or accumulators
    past the cap ride the dq/dkv split: ``flash_bwd_bshd_dq`` / ``_dkv``
    (``flash_bwd_bshd_win_dq`` / ``_win_dkv`` with ``window``), with D and
    its carrier built by XLA, per-q-head fp32 dk/dv partials and
    :func:`_group_sum`.

    A value head of another width than q's (v, o, do (…, dv)) and a second
    score term (``second``, as :func:`flash_fwd_bshd` takes it; the call is
    ``flash_bwd_bshd_mla_fused`` and returns dq2, dk2 after dv) exist in the
    one-pass kernel only: ask :func:`bshd_two_width_fits` first. dk2 is
    summed over all the q heads of its key in VMEM, as dk over its group."""
    b, sq, h, d = q.shape
    sk, h_kv = k.shape[1], k.shape[2]
    wv = v.shape[3]
    group = h // h_kv
    bq, bk = _bias_blocks(bias, bq, bk)
    bq, bk = _fit_block(sq, bq), _fit_block(sk, bk)
    nq, nk = _blocks(sq, bq), _blocks(sk, bk)
    off = sk - sq
    _check_window(window, causal, bias, rel_bias)
    _check_second(second, window, bias, rel_bias, kv_lens, dropout_rate)
    d2 = 0 if second is None else second[0].shape[-1]
    if _fused_bwd_fits(bias, rel_bias, sq, sk, d, bq, bk, q.dtype.itemsize, wv, d2):
        # folded (b, s, h·d) views — free bitcasts (see flash_fwd_bshd)
        dq, dk, dv, *d_second = _flash_bwd_fused(
            q.reshape(b, sq, h * d), k.reshape(b, sk, h_kv * d),
            v.reshape(b, sk, h_kv * wv), o.reshape(b, sq, h * wv), lse,
            do.reshape(b, sq, h * wv), h=h, h_kv=h_kv, d=d, packed=False,
            scale=scale, causal=causal, kv_lens=kv_lens, bq=bq, bk=bk,
            interpret=interpret, dropout_rate=dropout_rate,
            dropout_seed=dropout_seed, window=window, second=second)
        return (dq.reshape(b, sq, h, d), dk.reshape(b, sk, h_kv, d),
                dv.reshape(b, sk, h_kv, wv), *d_second)
    if second is not None or wv != d:
        raise NotImplementedError(
            "two head widths or a second score term: the one-pass backward "
            "only (bshd_two_width_fits)")
    banded, reach = window is not None, (window or 1) - 1
    k_steps, kv_block = _band_walk(banded, nq, bq, bk, nk, off, reach, 0)
    q_steps, q_block = _band_walk(banded, nk, bk, bq, nq, -off, 0, reach)
    hb = 0 if bias is None else bias.shape[0]
    rel, rel_static = (None, None) if rel_bias is None else (
        rel_bias[:2], rel_bias[2])
    rhb = 0 if rel is None else rel[0].shape[0]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    # (b, sq, h) -> the (b, h, sq, LANES) carrier the kernels read rowwise
    lse4 = lse if lse.ndim == 4 else _expand_rows(lse)
    delta4 = _expand_rows(delta.transpose(0, 2, 1))
    # folded (b, s, h·d) views — free bitcasts, head = block index (see
    # flash_fwd_bshd)
    q3 = q.reshape(b, sq, h * d)
    k3 = k.reshape(b, sk, h_kv * d)
    v3 = v.reshape(b, sk, h_kv * d)
    do3 = do.reshape(b, sq, h * d)

    def q_spec(index_map):
        return pl.BlockSpec((1, bq, d), index_map)

    def kv_spec(index_map):
        return pl.BlockSpec((1, bk, d), index_map)

    def row_spec(index_map):
        return pl.BlockSpec((1, 1, bq, _LSE_LANES), index_map)

    qm = lambda t, i, j, h=h: (t // h, i, t % h)  # noqa: E731
    km = lambda t, i, j, h=h, g=group: (  # noqa: E731
        t // h, kv_block(i, j), (t % h) // g)
    rm = lambda t, i, j, h=h: (t // h, t % h, i, 0)  # noqa: E731
    varlen = kv_lens is not None
    extra_specs, extra_args = _tail_operands(
        kv_lens, b, dropout_rate, dropout_seed,
        lambda t, i, j, h=h: (t // h, 0, 0),
        bias, lambda t, i, j, hb=hb: (t % hb, i, j), (1, bq, bk),
        rel, lambda t, i, j, rhb=rhb: t % rhb)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=k_steps, off=off, varlen=varlen,
                          bshd=True, rate=dropout_rate,
                          has_bias=bias is not None, rel=rel_static,
                          window=window),
        name="flash_bwd_bshd_dq" if window is None else "flash_bwd_bshd_win_dq",
        grid=(b * h, nq, k_steps),
        in_specs=[q_spec(qm), kv_spec(km), kv_spec(km), q_spec(qm),
                  row_spec(rm), row_spec(rm)] + extra_specs,
        out_specs=q_spec(qm),
        out_shape=jax.ShapeDtypeStruct((b, sq, h * d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_split_bwd_vmem_limit(
                d, bq, bk, q.dtype.itemsize, q.dtype.itemsize)),
        interpret=interpret,
    )(q3, k3, v3, do3, lse4, delta4, *extra_args)

    qm2 = lambda t, j, i, h=h: (t // h, q_block(j, i), t % h)  # noqa: E731
    km2 = lambda t, j, i, h=h, g=group: (t // h, j, (t % h) // g)  # noqa: E731
    rm2 = lambda t, j, i, h=h: (t // h, t % h, q_block(j, i), 0)  # noqa: E731
    # grouped kv: per-q-head fp32 partials at q-head positions, summed per
    # kv group outside (same rationale as flash_bwd)
    dkv_dtypes = (jnp.float32, jnp.float32) if group > 1 else (k.dtype,
                                                               v.dtype)
    dkm = lambda t, j, i, h=h: (t // h, j, t % h)  # noqa: E731
    extra_specs2, _ = _tail_operands(
        kv_lens, b, dropout_rate, dropout_seed,
        lambda t, j, i, h=h: (t // h, 0, 0),
        bias, lambda t, j, i, hb=hb: (t % hb, i, j), (1, bq, bk),
        rel, lambda t, j, i, rhb=rhb: t % rhb)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=q_steps, off=off, varlen=varlen,
                          bshd=True, rate=dropout_rate,
                          has_bias=bias is not None, rel=rel_static,
                          window=window, nq_all=nq),
        name="flash_bwd_bshd_dkv" if window is None else "flash_bwd_bshd_win_dkv",
        grid=(b * h, nk, q_steps),
        in_specs=[q_spec(qm2), kv_spec(km2), kv_spec(km2), q_spec(qm2),
                  row_spec(rm2), row_spec(rm2)] + extra_specs2,
        out_specs=[kv_spec(dkm), kv_spec(dkm)],
        out_shape=[
            jax.ShapeDtypeStruct((b, sk, h * d), dkv_dtypes[0]),
            jax.ShapeDtypeStruct((b, sk, h * d), dkv_dtypes[1]),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_split_bwd_vmem_limit(
                d, bq, bk, q.dtype.itemsize, jnp.dtype(dkv_dtypes[0]).itemsize)),
        interpret=interpret,
    )(q3, k3, v3, do3, lse4, delta4, *extra_args)
    dq = dq.reshape(b, sq, h, d)
    if group > 1:
        dk = _group_sum(dk, h_kv, group, d, k.dtype)
        dv = _group_sum(dv, h_kv, group, d, v.dtype)
    dk = dk.reshape(b, sk, h_kv, d)
    dv = dv.reshape(b, sk, h_kv, d)
    if rel is not None:
        # dtable: global q-head row r = b·rhb + th over the folded
        # (b, s, h·d) operands via (r // h, ·, r % h)
        nb = (b * h) // rhb
        bq, bk, nq, nk = _dtable_blocks(sq, sk, bq, bk)
        qmap = lambda th, i, j, bi, rhb=rhb, h=h: (  # noqa: E731
            (bi * rhb + th) // h, i, (bi * rhb + th) % h)
        kmap = lambda th, i, j, bi, rhb=rhb, h=h, g=group: (  # noqa: E731
            (bi * rhb + th) // h, j, ((bi * rhb + th) % h) // g)
        rmap = lambda th, i, j, bi, rhb=rhb, h=h: (  # noqa: E731
            (bi * rhb + th) // h, (bi * rhb + th) % h, i, 0)
        tab_spec, tab_arg = _rel_table_operand(
            rel[0], lambda th, i, j, bi: th)
        dt_specs = [
            pl.BlockSpec((1, bq, d), qmap),
            pl.BlockSpec((1, bk, d), kmap),
            pl.BlockSpec((1, bk, d), kmap),
            pl.BlockSpec((1, bq, d), qmap),
            pl.BlockSpec((1, 1, bq, _LSE_LANES), rmap),
            pl.BlockSpec((1, 1, bq, _LSE_LANES), rmap),
            tab_spec,
            _SMEM_SPEC,
        ]
        dt_args = [q3, k3, v3, do3, lse4, delta4, tab_arg, rel[1]]
        if varlen:
            dt_specs.append(pl.BlockSpec(
                (1, 1, _LSE_LANES),
                lambda th, i, j, bi, rhb=rhb, h=h: (
                    (bi * rhb + th) // h, 0, 0)))
            dt_args.append(_kvlen_rows(kv_lens, b))
        if dropout_rate > 0.0:
            dt_specs.append(_SMEM_SPEC)
            dt_args.append(_seed_operand(dropout_seed))
        dtable = _dtable_pallas(
            dt_args, dt_specs, hb=rhb, nq=nq, nk=nk, nb=nb, bq=bq, bk=bk,
            scale=scale, causal=causal, off=sk - sq, varlen=varlen,
            bshd=True, rate=dropout_rate, rel=rel_static,
            interpret=interpret)
        return dq, dk, dv, dtable
    if bias is None:
        return dq, dk, dv
    # dbias: batch-innermost grid; global q-head row r = b·hb + th maps to
    # the folded (b, s, h·d) operands via (r // h, ·, r % h)
    nb = (b * h) // hb
    qmap = lambda th, i, j, bi, hb=hb, h=h: (  # noqa: E731
        (bi * hb + th) // h, i, (bi * hb + th) % h)
    kmap = lambda th, i, j, bi, hb=hb, h=h, g=group: (  # noqa: E731
        (bi * hb + th) // h, j, ((bi * hb + th) % h) // g)
    rmap = lambda th, i, j, bi, hb=hb, h=h: (  # noqa: E731
        (bi * hb + th) // h, (bi * hb + th) % h, i, 0)
    db_specs = [
        pl.BlockSpec((1, bq, d), qmap),
        pl.BlockSpec((1, bk, d), kmap),
        pl.BlockSpec((1, bk, d), kmap),
        pl.BlockSpec((1, bq, d), qmap),
        pl.BlockSpec((1, 1, bq, _LSE_LANES), rmap),
        pl.BlockSpec((1, 1, bq, _LSE_LANES), rmap),
        pl.BlockSpec((1, bq, bk), lambda th, i, j, bi: (th, i, j)),
    ]
    db_args = [q3, k3, v3, do3, lse4, delta4, bias]
    if varlen:
        db_specs.append(pl.BlockSpec(
            (1, 1, _LSE_LANES),
            lambda th, i, j, bi, hb=hb, h=h: ((bi * hb + th) // h, 0, 0)))
        db_args.append(_kvlen_rows(kv_lens, b))
    if dropout_rate > 0.0:
        db_specs.append(_SMEM_SPEC)
        db_args.append(_seed_operand(dropout_seed))
    dbias = _dbias_pallas(
        db_args, db_specs, hb=hb, sq=sq, sk=sk, nq=nq, nk=nk, nb=nb,
        bq=bq, bk=bk, scale=scale, causal=causal, off=sk - sq,
        varlen=varlen, bshd=True, rate=dropout_rate, interpret=interpret)
    return dq, dk, dv, dbias
