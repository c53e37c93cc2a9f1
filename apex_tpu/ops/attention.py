"""Flash attention (functional) + ring / Ulysses sequence parallelism.

``flash_attention`` supersedes the reference's ``apex.contrib.fmha``
(``apex/contrib/fmha/fmha.py:33-76``: fp16, seq≤512 only) and the fused MHA
cores of ``apex.contrib.multihead_attn``: one blockwise kernel, any length,
causal or full, bf16/fp32.

``ring_attention`` and ``ulysses_attention`` are the long-context
capabilities the reference lacks entirely (SURVEY.md §5 "Long-context: not
present"; §2.3 lists both CP and Ulysses as absent strategies). Ring: Q/K/V
sharded over the ``cp`` mesh axis along sequence; KV shards rotate via
``ppermute`` while each device folds incoming *flash-kernel* (o, lse)
pieces into the online-softmax state — O(s_local·d) memory, comm hidden
behind per-step compute, causal load balanced by zigzag stripe sharding
(see :func:`ring_attention`). Ulysses: two ``all_to_all``s swap sequence
sharding for head sharding so each device runs *unmodified* flash attention
over the full sequence for its head subset — cheaper comm than ring when
heads ≥ devices (2 all-to-alls of the activations vs cp rotations of KV).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

import numpy as _np

from apex_tpu.amp.lists import apply_op_rules
from apex_tpu.ops import _backend
from apex_tpu.ops.pallas import attention as _k
from apex_tpu.ops.pallas.attention import relative_position_bucket  # noqa: F401 (public re-export)
from apex_tpu.parallel import mesh as mesh_lib


def _float0_like(x):
    """Zero cotangent for an integer primal (kv_lens, dropout seeds):
    custom-VJP backwards must return float0 for ints, None for absent."""
    return (None if x is None
            else _np.zeros(jnp.shape(x), jax.dtypes.float0))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BucketedBias:
    """T5 bucketed relative position bias as a first-class attention
    operand: the TINY ``(num_buckets, heads)`` table plus its bucketing
    config, recomputed per score tile INSIDE the flash kernels — the
    O(h·s²) HBM of a materialized ``(h, sq, sk)`` bias array collapses to
    O(num_buckets·h) (~1.6 GB → ~1 KB at s=8192, h=6), and because every
    tile derives its bias from GLOBAL coordinates (``q_offset`` /
    ``k_offset``: the global position of this shard's first query/key
    row), the same operand is computable per block under ANY sequence
    sharding — which is what lets ``ring_attention`` and
    ``ulysses_attention`` accept it (the materialized array cannot ride
    cp without replicating O(s²) per device).

    ``bidirectional=True`` is the T5 encoder bucketing (sign-split
    buckets), ``False`` the causal decoder form (future clamps to bucket
    0). Differentiable in ``table`` (the flash custom-VJPs return the
    bucket-table cotangent, computed in-kernel by the dtable kernel on
    the Pallas path); offsets are integer positions (float0 cotangents).

    Pass an instance as ``bias=`` to :func:`flash_attention` (both
    layouts), :func:`ring_attention`, :func:`ulysses_attention`, or
    ``decode_attention``. The packed ``fused_qkv_attention`` path takes
    materialized arrays only."""

    table: jax.Array                 # (num_buckets, heads)
    bidirectional: bool = False
    max_distance: int = 128
    q_offset: Any = 0                # global position of query row 0
    k_offset: Any = 0                # global position of key row 0

    def tree_flatten(self):
        return ((self.table, self.q_offset, self.k_offset),
                (self.bidirectional, self.max_distance))

    @classmethod
    def tree_unflatten(cls, aux, children):
        table, q_off, k_off = children
        return cls(table, aux[0], aux[1], q_off, k_off)

    @property
    def num_buckets(self) -> int:
        return self.table.shape[0]

    @property
    def heads(self) -> int:
        return self.table.shape[1]

    @property
    def static(self):
        """The kernels' static bucketing triple."""
        return (self.num_buckets, self.bidirectional, self.max_distance)

    def shifted(self, dq, dk) -> "BucketedBias":
        """Same table, offsets advanced by (dq, dk) — how the cp paths
        hand each stripe piece its global window."""
        return BucketedBias(self.table, self.bidirectional,
                            self.max_distance,
                            self.q_offset + dq, self.k_offset + dk)

    def kernel_operands(self):
        """(table (h, 128) fp32 head-major, offsets (2,) int32, static) —
        the Pallas kernels' ``rel_bias`` triple (one (1, 128) VMEM row per
        head; buckets pad the lane dim)."""
        nb, h = self.table.shape
        tab = jnp.zeros((h, _k._REL_LANES), jnp.float32)
        tab = tab.at[:, :nb].set(self.table.astype(jnp.float32).T)
        off = jnp.stack([
            jnp.asarray(self.q_offset, jnp.int32).reshape(()),
            jnp.asarray(self.k_offset, jnp.int32).reshape(())])
        return tab, off, self.static

    def materialize(self, sq, sk) -> jax.Array:
        """The (heads, sq, sk) fp32 array this operand abbreviates — the
        XLA-fallback/oracle form (O(h·sq·sk): only for fallbacks and
        tests; the kernels never build it)."""
        rel = ((jnp.asarray(self.k_offset, jnp.int32)
                + jnp.arange(sk, dtype=jnp.int32))[None, :]
               - (jnp.asarray(self.q_offset, jnp.int32)
                  + jnp.arange(sq, dtype=jnp.int32))[:, None])
        buckets = relative_position_bucket(
            rel, bidirectional=self.bidirectional,
            num_buckets=self.num_buckets, max_distance=self.max_distance)
        return self.table.astype(jnp.float32)[buckets].transpose(2, 0, 1)


def _bias_rows(bias) -> int:
    """Leading (row) extent of the bias operand — table heads for the
    bucketed form, hb for a materialized array — for the r % hb divide
    checks shared by both forms."""
    return bias.heads if isinstance(bias, BucketedBias) else bias.shape[0]


def _validate_bucketed(bias: BucketedBias) -> None:
    if bias.table.ndim != 2:
        raise ValueError(
            f"BucketedBias.table must be (num_buckets, heads); got "
            f"{bias.table.shape}")
    nb = bias.num_buckets
    if not 2 <= nb <= _k._REL_LANES:
        raise ValueError(
            f"num_buckets must be in [2, {_k._REL_LANES}] (the table pads "
            f"one 128-lane VMEM row); got {nb}")
    if bias.bidirectional and nb % 2:
        raise ValueError(
            f"bidirectional bucketing splits the range by sign and needs "
            f"an even num_buckets; got {nb}")


def _bucketed_table_grad(bias: BucketedBias, dbias_arr: jax.Array):
    """(num_buckets, heads) table cotangent from a materialized dbias
    (heads, sq, sk) — the gather's VJP (scatter-add by bucket), used by
    the XLA fallback backward (the Pallas path gets dtable straight from
    the in-kernel dtable kernel)."""
    sq, sk = dbias_arr.shape[1], dbias_arr.shape[2]
    _, vjp = jax.vjp(
        lambda t: dataclasses.replace(bias, table=t).materialize(sq, sk),
        bias.table)
    (dtable,) = vjp(dbias_arr)
    return dtable


# --- single-device flash attention -------------------------------------------

def flash_auto_crossover(head_dim: int) -> int:
    """Minimum kv sequence length at which ``impl='auto'`` picks the Pallas
    kernel — measured end-to-end on v5e (see :func:`flash_attention`'s
    docstring table): 1024 at head_dim 64, 512 from head_dim 128 (full MXU
    lanes lower the kernel's break-even)."""
    return 512 if head_dim >= 128 else 1024

def masked_scores(q, k, scale, causal, kv_lens=None, bias=None, window=None):
    """fp32 scaled scores over (..., seq, head_dim) with the bottom-right-
    aligned causal mask (last ``sq`` query rows of an ``sk``-long context)
    and optional per-row valid kv lengths (padding). ``kv_lens`` requires
    the flattened 3D layout (rows, seq, d) with one length per row.
    ``bias`` (hb, sq, sk): additive score bias, row ``r`` reading bias row
    ``r % hb`` (same contract as the Pallas kernels) — added to the scaled
    scores BEFORE the masks; requires the 3D layout. ``window`` (with
    ``causal``): a query sees its last ``window`` keys only, itself among
    them."""
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * scale
    sq, sk = s.shape[-2], s.shape[-1]
    if bias is not None:
        if s.ndim != 3:
            raise ValueError(
                "bias requires 3D (rows, sq, sk) scores; flatten leading "
                "dims to rows first")
        hb = bias.shape[0]
        # rows r = b·hb + th share bias row th — the reshape groups them
        s = (s.reshape(-1, hb, sq, sk)
             + bias.astype(jnp.float32)).reshape(s.shape)
    if causal:
        q_pos = jnp.arange(sq)[:, None] + (sk - sq)
        mask = jnp.arange(sk)[None, :] <= q_pos
        if window is not None:
            mask = mask & (jnp.arange(sk)[None, :] > q_pos - window)
        s = jnp.where(mask, s, _k.NEG_INF)
    if kv_lens is not None:
        if s.ndim != 3:
            raise ValueError(
                "kv_lens masking requires 3D (rows, sq, sk) scores; flatten "
                "leading dims to rows first")
        s = jnp.where(jnp.arange(sk)[None, None, :] < kv_lens[:, None, None],
                      s, _k.NEG_INF)
    return s


def _dropout_keep_dense(seed, bh, sq, sk, rate):
    """(bh, sq, sk) BOOL keep mask from the SAME counter-based hash the
    Pallas kernels evaluate blockwise (``pallas.attention.dropout_keep``)
    — kernel and XLA dispatch produce BIT-IDENTICAL masks, so the impl
    choice never changes a training run. Bool (not a pre-scaled fp32
    multiplier): the 1/(1-rate) rescale folds into each use site's
    ``where`` so XLA fuses the mask into its consumer instead of holding
    a persistent fp32 O(s²) tensor on the fallback path (ADVICE r4)."""
    t = jnp.arange(bh, dtype=jnp.int32)[:, None, None]
    rows = jnp.arange(sq, dtype=jnp.int32)[None, :, None]
    cols = jnp.arange(sk, dtype=jnp.int32)[None, None, :]
    return _k.dropout_keep(jnp.asarray(seed, jnp.int32), t, rows, cols,
                           rate)


def _dropout_apply_dense(x, keep, rate):
    """mask-and-rescale fused in one ``where`` (see above)."""
    return jnp.where(keep, x * jnp.float32(1.0 / (1.0 - rate)), 0.0)


def _xla_attention(q, k, v, scale, causal, kv_lens=None,
                   dropout_rate=0.0, dropout_seed=None, bias=None,
                   window=None):
    s = masked_scores(q, k, scale, causal, kv_lens, bias, window)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    if dropout_rate > 0.0:
        # probs dropout: the normalizer (lse) stays un-dropped, the
        # weighted sum takes the masked, rescaled probabilities
        p = _dropout_apply_dense(
            p, _dropout_keep_dense(dropout_seed, s.shape[0], s.shape[-2],
                                   s.shape[-1], dropout_rate),
            dropout_rate)
    o = jnp.einsum("bqk,bkd->bqd", p.astype(q.dtype), v)
    if kv_lens is not None:
        # fully-masked rows: uniform-softmax garbage -> zeros, and pin lse
        # to 0 so backward's exp(NEG_INF - lse) underflows to 0 (the kernel
        # path's dead-row convention)
        dead = (kv_lens == 0)[:, None]
        o = jnp.where(dead[..., None], 0.0, o).astype(q.dtype)
        lse = jnp.where(dead, 0.0, lse)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _flash_core(q, k, v, bias, kv_lens, dropout_seed, scale, causal,
                use_pallas, dropout_rate):
    o, _ = _flash_fwd_res(q, k, v, bias, kv_lens, dropout_seed, scale,
                          causal, use_pallas, dropout_rate)
    return o


def _flash_fwd_res(q, k, v, bias, kv_lens, dropout_seed, scale, causal,
                   use_pallas, dropout_rate):
    bucketed = isinstance(bias, BucketedBias)
    if use_pallas:
        # full_lse: the residual keeps the (bh, sq, LANES) carrier so the
        # backward kernel reads it as-is (no slice/re-broadcast round trip)
        o, lse = _k.flash_fwd(
            q, k, v, scale=scale, causal=causal, kv_lens=kv_lens,
            bias=None if bucketed else bias,
            rel_bias=bias.kernel_operands() if bucketed else None,
            full_lse=True, interpret=_backend.interpret_mode(),
            dropout_rate=dropout_rate, dropout_seed=dropout_seed,
        )
    else:
        group = q.shape[0] // k.shape[0]
        kf = jnp.repeat(k, group, 0) if group > 1 else k
        vf = jnp.repeat(v, group, 0) if group > 1 else v
        # XLA fallback: the bucketed operand materializes (the O(s²) array
        # exists ONLY on this path — small-seq / non-kernel shapes)
        bias_arr = (bias.materialize(q.shape[1], k.shape[1]) if bucketed
                    else bias)
        o, lse = _xla_attention(q, kf, vf, scale, causal, kv_lens,
                                dropout_rate, dropout_seed, bias_arr)
    return o, (q, k, v, o, lse)


def _flash_fwd(q, k, v, bias, kv_lens, dropout_seed, scale, causal,
               use_pallas, dropout_rate):
    o, res = _flash_fwd_res(q, k, v, bias, kv_lens, dropout_seed, scale,
                            causal, use_pallas, dropout_rate)
    return o, (res, bias, kv_lens, dropout_seed)


def _flash_bwd_impl(q, k, v, o, lse, do, kv_lens, scale, causal, use_pallas,
                    dropout_rate=0.0, dropout_seed=None, bias=None,
                    window=None):
    """(dq, dk, dv, dbias) from saved (o, lse) — dbias is None when no bias
    rode the forward. With a *global* lse this is also the per-shard
    backward of distributed (ring) attention: p = exp(s − lse) and
    Δ = rowsum(do·o_final) are exact per shard, so each shard's ds —
    and hence its dq/dk/dv contribution — needs no cross-shard state.

    Dropout chain (S → P=softmax → Pd=mask∘P/(1-r) → O=Pd·V): the mask
    regenerates from the same counter hash as forward; dV = Pdᵀ·dO and
    dS = P ∘ (mask/(1-r) ∘ (dO·Vᵀ) − Δ) — Δ = rowsum(dO∘O) already equals
    rowsum(Pd ∘ dPd), so only the dPd term re-masks.

    Bias: dbias = Σ over the rows sharing each bias row of the UNSCALED
    dS (bias enters S additively after the 1/√d scale). With a
    :class:`BucketedBias` the fourth output is the (num_buckets, heads)
    TABLE cotangent instead (in-kernel dtable on the Pallas path; gather
    VJP on the materialized fallback).

    ``window``: the XLA composition only (the seq-major layout's oracle;
    the flat kernels take no window)."""
    bucketed = isinstance(bias, BucketedBias)
    if use_pallas:
        assert window is None
        out = _k.flash_bwd(
            q, k, v, o, lse, do, scale=scale, causal=causal, kv_lens=kv_lens,
            bias=None if bucketed else bias,
            rel_bias=bias.kernel_operands() if bucketed else None,
            interpret=_backend.interpret_mode(),
            dropout_rate=dropout_rate, dropout_seed=dropout_seed,
        )
        if bias is None:
            return (*out, None)
        if bucketed:
            dq, dk, dv, dtab_hm = out
            return dq, dk, dv, dtab_hm[:, :bias.num_buckets].T
        return out
    group = q.shape[0] // k.shape[0]
    kf = jnp.repeat(k, group, 0) if group > 1 else k
    vf = jnp.repeat(v, group, 0) if group > 1 else v
    bias_arr = (bias.materialize(q.shape[1], k.shape[1]) if bucketed
                else bias)
    s = masked_scores(q, kf, scale, causal, kv_lens, bias_arr, window)
    p = jnp.exp(s - lse[..., None])
    dof = do.astype(jnp.float32)
    if dropout_rate > 0.0:
        keep = _dropout_keep_dense(
            dropout_seed, s.shape[0], s.shape[-2], s.shape[-1], dropout_rate)
        pd = _dropout_apply_dense(p, keep, dropout_rate)
    else:
        pd = p
    dv = jnp.einsum("bqk,bqd->bkd", pd, dof)
    dp = jnp.einsum("bqd,bkd->bqk", dof, vf.astype(jnp.float32))
    if dropout_rate > 0.0:
        dp = _dropout_apply_dense(dp, keep, dropout_rate)
    delta = jnp.sum(dof * o.astype(jnp.float32), axis=-1, keepdims=True)
    ds_pre = p * (dp - delta)  # the unscaled dS (the bias cotangent)
    dbias = None
    if bias is not None:
        hb, sq, sk_ = bias_arr.shape
        dbias = ds_pre.reshape(-1, hb, sq, sk_).sum(0)
        if bucketed:
            dbias = _bucketed_table_grad(bias, dbias)
    ds = ds_pre * scale
    dq = jnp.einsum("bqk,bkd->bqd", ds, kf.astype(jnp.float32)).astype(q.dtype)
    dk = jnp.einsum("bqk,bqd->bkd", ds, q.astype(jnp.float32))
    if group > 1:
        # per-q-head kv grads -> sum each kv group
        sk, d = k.shape[1], k.shape[2]
        dk = dk.reshape(-1, group, sk, d).sum(1)
        dv = dv.reshape(-1, group, sk, d).sum(1)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype), dbias


def _bias_cotangent(bias, dbias):
    """Package the 4th backward output as the bias primal's cotangent:
    arrays get the array grad in their own dtype; a BucketedBias gets a
    BucketedBias whose table is the (num_buckets, heads) grad and whose
    integer offsets carry float0."""
    if bias is None:
        return None
    if isinstance(bias, BucketedBias):
        return BucketedBias(
            dbias.astype(bias.table.dtype), bias.bidirectional,
            bias.max_distance, _float0_like(bias.q_offset),
            _float0_like(bias.k_offset))
    return dbias.astype(bias.dtype)


def _flash_bwd(scale, causal, use_pallas, dropout_rate, res_pack, do):
    res, bias, kv_lens, dropout_seed = res_pack
    q, k, v, o, lse = res
    dq, dk, dv, dbias = _flash_bwd_impl(
        q, k, v, o, lse, do, kv_lens, scale, causal, use_pallas,
        dropout_rate, dropout_seed, bias)
    return (dq, dk, dv, _bias_cotangent(bias, dbias),
            _float0_like(kv_lens), _float0_like(dropout_seed))


_flash_core.defvjp(_flash_fwd, _flash_bwd)


# --- seq-major (bshd) core ----------------------------------------------------

def bshd_kernel_ok(sq: int, sk: int, h: int, d: int, dtype) -> bool:
    """Mosaic eligibility for the seq-major (folded) kernels — shared by
    ``flash_attention(layout='bshd')``, the ring, Ulysses and BERT, and the
    first half of :func:`packed_kernel_ok`, so the rule lives in ONE place.
    The folded (b, s, h·d) views take d-wide column blocks, so d must tile
    the 128-lane rule itself (d == 64 only passes when it IS the folded dim,
    i.e. a single head); f16 has no Mosaic support at all."""
    return (sq % 128 == 0 and sk % 128 == 0
            and (d % 128 == 0 or (h == 1 and d == 64))
            and dtype != jnp.float16)


def packed_kernel_ok(s: int, h: int, h_kv: int, d: int, dtype) -> bool:
    """Mosaic eligibility for :func:`fused_qkv_attention` (the packed q|k|v
    buffer, self attention of ``s`` positions) — the GPT fused-path gate.
    Either :func:`bshd_kernel_ok`'s rule, or the PAIR rule: heads of 64 ride
    two to a 128-lane block when there is an even number of them and
    ``h == h_kv`` (``pallas.attention.packed_pair``: under a group of q heads
    the two heads of a q pair would share one kv head on the wrong lanes —
    grouped heads of 64 keep the flat kernels), ``s`` tiles by 128 and fits
    the one-pass backward (``packed_pair_fits``: about 16 k positions), and
    the dtype is not f16. A pair call takes no score bias. Shapes and dtype
    only: nothing else chooses the path."""
    return bshd_kernel_ok(s, s, h, d, dtype) or (
        _k.packed_pair(h, h_kv, d) and s % 128 == 0
        and dtype != jnp.float16
        and _k.packed_pair_fits(s, jnp.dtype(dtype).itemsize))


def bshd_qkv_projection(x, weight, bias, h, h_kv, d):
    """(b, s, H) activations through a PACKED q|k|v weight ((h+2·h_kv)·d,
    H), features ordered q-heads|k-heads|v-heads — straight to the
    seq-major (b, s, heads, d) layout the bshd kernels read with no layout
    copy. The ONE place the packed-layout slicing lives (GPT and BERT both
    ride it; a layout change edits one function)."""
    H = weight.shape[-1]
    wq = weight[:h * d].reshape(h, d, H)
    wk = weight[h * d:(h + h_kv) * d].reshape(h_kv, d, H)
    wv = weight[(h + h_kv) * d:].reshape(h_kv, d, H)
    q = jnp.einsum("bsH,hdH->bshd", x, wq)
    k = jnp.einsum("bsH,hdH->bshd", x, wk)
    v = jnp.einsum("bsH,hdH->bshd", x, wv)
    if bias is not None:
        q = q + bias[:h * d].reshape(h, d)
        k = k + bias[h * d:(h + h_kv) * d].reshape(h_kv, d)
        v = v + bias[(h + h_kv) * d:].reshape(h_kv, d)
    return q, k, v


def bshd_output_projection(ctx, weight, h, d):
    """(b, s, h, d) attention context through the output weight (O, h·d),
    contracted directly over (heads, d) — no transpose back to flat."""
    return jnp.einsum("bshd,Hhd->bsH", ctx, weight.reshape(-1, h, d))


def _to_bh(x):  # (b, s, h, d) -> (b*h, s, d) for the XLA fallback
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bh(x, b, h):  # (b*h, s, d) -> (b, s, h, d)
    s, d = x.shape[1], x.shape[2]
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _flash_core_bshd(q, k, v, bias, kv_lens, dropout_seed, scale, causal,
                     use_pallas, dropout_rate, window=None):
    o, _ = _flash_fwd_res_bshd(q, k, v, bias, kv_lens, dropout_seed, scale,
                               causal, use_pallas, dropout_rate, window)
    return o


def _expand_lens_bh(kv_lens, h):
    """(b,) per-batch lengths -> (b*h,) per-row for the flat XLA path
    (matches _to_bh's b-major row order)."""
    return None if kv_lens is None else jnp.repeat(kv_lens, h)


def _flash_fwd_res_bshd(q, k, v, bias, kv_lens, dropout_seed, scale, causal,
                        use_pallas, dropout_rate, window=None):
    bucketed = isinstance(bias, BucketedBias)
    if use_pallas:
        # carrier residual, same rationale as _flash_fwd_res
        o, lse = _k.flash_fwd_bshd(
            q, k, v, scale=scale, causal=causal, kv_lens=kv_lens,
            bias=None if bucketed else bias,
            rel_bias=bias.kernel_operands() if bucketed else None,
            full_lse=True, interpret=_backend.interpret_mode(),
            dropout_rate=dropout_rate, dropout_seed=dropout_seed,
            window=window)
    else:
        b, h = q.shape[0], q.shape[2]
        group = h // k.shape[2]
        # flat repeat matches the grouped row order (q row b·h + h_i reads
        # kv row (b·h + h_i)//group) — same expansion _flash_bwd_impl uses;
        # bias rows keep the r % hb contract under the b-major flatten
        kf = _to_bh(k)
        vf = _to_bh(v)
        if group > 1:
            kf = jnp.repeat(kf, group, 0)
            vf = jnp.repeat(vf, group, 0)
        bias_arr = (bias.materialize(q.shape[1], k.shape[1]) if bucketed
                    else bias)
        o3, lse3 = _xla_attention(_to_bh(q), kf, vf, scale, causal,
                                  _expand_lens_bh(kv_lens, h),
                                  dropout_rate, dropout_seed, bias_arr,
                                  window)
        o = _from_bh(o3, b, h)
        lse = lse3.reshape(b, h, -1)
    return o, (q, k, v, o, lse)


# The forward kernel's results under the seq-major rules below, by the names
# a ``jax.checkpoint`` policy keeps them under (``save_only_these_names``):
# the backward rule reads both, so with both saved a recomputed block does
# not launch the forward kernel again. Outside ``jax.checkpoint`` a name
# lowers to nothing. The flat and packed rules name nothing.
FLASH_SAVED = ("flash_o", "flash_lse")


def _flash_saved(o, lse):
    return tuple(map(checkpoint_name, (o, lse), FLASH_SAVED))


def _flash_fwd_bshd(q, k, v, bias, kv_lens, dropout_seed, scale, causal,
                    use_pallas, dropout_rate, window=None):
    o, (*_, lse) = _flash_fwd_res_bshd(
        q, k, v, bias, kv_lens, dropout_seed, scale, causal, use_pallas,
        dropout_rate, window)
    # the rows: lane 0 of the kernel's (b, h, s, 8) carrier, which HBM pads
    # to 128 lanes; the one-pass backward reads rows, the split re-expands
    o, lse = _flash_saved(o, lse[..., 0] if lse.ndim == 4 else lse)
    return o, ((q, k, v, o, lse), bias, kv_lens, dropout_seed)


def _flash_bwd_bshd_impl(q, k, v, o, lse, do, kv_lens, scale, causal,
                         use_pallas, dropout_rate=0.0, dropout_seed=None,
                         bias=None, window=None):
    """(dq, dk, dv, dbias) for the seq-major layout — the bshd twin of
    :func:`_flash_bwd_impl`, same raw-cotangent contract: dbias is the
    UNcast fp32 bucket-table grad (BucketedBias) / fp32 dbias array /
    None — so cross-piece accumulators (the ring) sum full-precision
    partials and only the final custom-vjp cotangent casts to the
    primal's dtype."""
    bucketed = isinstance(bias, BucketedBias)
    if use_pallas:
        out = _k.flash_bwd_bshd(
            q, k, v, o, lse, do, scale=scale, causal=causal,
            kv_lens=kv_lens, bias=None if bucketed else bias,
            rel_bias=bias.kernel_operands() if bucketed else None,
            interpret=_backend.interpret_mode(),
            dropout_rate=dropout_rate, dropout_seed=dropout_seed,
            window=window)
        dq, dk, dv = out[:3]
        dbias = None
        if bias is not None:
            dbias = (out[3][:, :bias.num_buckets].T if bucketed
                     else out[3])
        return dq, dk, dv, dbias
    b, h = q.shape[0], q.shape[2]
    h_kv = k.shape[2]
    dq3, dk3, dv3, dbias = _flash_bwd_impl(
        _to_bh(q), _to_bh(k), _to_bh(v), _to_bh(o),
        lse.reshape(b * h, -1), _to_bh(do), _expand_lens_bh(kv_lens, h),
        scale, causal, use_pallas=False, dropout_rate=dropout_rate,
        dropout_seed=dropout_seed, bias=bias, window=window)
    return (_from_bh(dq3, b, h), _from_bh(dk3, b, h_kv),
            _from_bh(dv3, b, h_kv), dbias)


def _flash_bwd_bshd(scale, causal, use_pallas, dropout_rate, window,
                    res_pack, do):
    res, bias, kv_lens, dropout_seed = res_pack
    q, k, v, o, lse = res
    dq, dk, dv, dbias = _flash_bwd_bshd_impl(
        q, k, v, o, lse, do, kv_lens, scale, causal, use_pallas,
        dropout_rate, dropout_seed, bias, window)
    return (dq, dk, dv, _bias_cotangent(bias, dbias),
            _float0_like(kv_lens), _float0_like(dropout_seed))


_flash_core_bshd.defvjp(_flash_fwd_bshd, _flash_bwd_bshd)


# --- seq-major core with two head widths / a second score term -----------------

def _xla_two_width(q, k, v, q2, k2, scale, causal):
    """The composition the two-width kernels are held to: materialised fp32
    scores ``(q·kᵀ + q2·k2ᵀ) * scale`` over (b, s, h, d) operands, every
    narrower head axis repeated to q's. Differentiated by JAX."""
    h = q.shape[2]
    wide = lambda x: jnp.repeat(x, h // x.shape[2], axis=2)  # noqa: E731
    s = jnp.einsum("bqhd,bkhd->bhqk", q, wide(k), preferred_element_type=jnp.float32)
    if q2 is not None:
        s = s + jnp.einsum("bqhd,bkhd->bhqk", q2, wide(k2),
                           preferred_element_type=jnp.float32)
    s = s * scale
    if causal:
        sq, sk = s.shape[-2:]
        s = jnp.where(jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None] + (sk - sq),
                      s, _k.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), wide(v))


_head_major = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731  (b, s, h, d) <-> (b, h, s, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_core_two_width(q, k, v, q2, k2, scale, causal, use_pallas):
    return _flash_fwd_two_width(q, k, v, q2, k2, scale, causal, use_pallas)[0]


def _flash_fwd_two_width(q, k, v, q2, k2, scale, causal, use_pallas):
    if not use_pallas:
        return _xla_two_width(q, k, v, q2, k2, scale, causal), (q, k, v, q2, k2)
    # the narrow second term rides head-major (see pallas.flash_fwd_bshd);
    # XLA folds the transposes into what made q2 and k2 (a rotary embedding)
    second = None if q2 is None else (_head_major(q2), _head_major(k2))
    o, lse = _flash_saved(*_k.flash_fwd_bshd(
        q, k, v, scale=scale, causal=causal, full_lse=True,
        interpret=_backend.interpret_mode(), second=second))
    return o, (q, k, v, second, o, lse)


def _flash_bwd_two_width(scale, causal, use_pallas, res, do):
    if not use_pallas:
        q, k, v, q2, k2 = res
        return jax.vjp(lambda *a: _xla_two_width(*a, scale, causal), q, k, v, q2, k2)[1](do)
    q, k, v, second, o, lse = res
    dq, dk, dv, *d_second = _k.flash_bwd_bshd(
        q, k, v, o, lse, do, scale=scale, causal=causal,
        interpret=_backend.interpret_mode(), second=second)
    return (dq, dk, dv, *(map(_head_major, d_second) if second else (None, None)))


_flash_core_two_width.defvjp(_flash_fwd_two_width, _flash_bwd_two_width)


def _two_width_attention(q, k, v, second, scale, causal, impl):
    """``flash_attention(layout='bshd')`` where v's head is not q's width or a
    second score term is given: checks, the kernel rule, the core."""
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError(f"layout='bshd' takes (b, s, h, d) operands; got "
                         f"{q.shape} / {k.shape} / {v.shape}")
    if q.shape[2] % k.shape[2] or k.shape[:3] != v.shape[:3]:
        raise ValueError(f"kv heads ({k.shape[2]}) must divide q heads ({q.shape[2]}) "
                         f"with matching batch/seq dims")
    if causal and q.shape[1] > k.shape[1]:
        raise ValueError(f"causal attention requires sq <= sk; got "
                         f"sq={q.shape[1]} > sk={k.shape[1]}")
    q2, k2 = second if second is not None else (None, None)
    b, sq, h, d = q.shape
    sk, dv = k.shape[1], v.shape[3]
    d2 = 0 if q2 is None else q2.shape[3]
    if q2 is not None and (
            q2.ndim != 4 or k2.ndim != 4 or
            q2.shape[:3] != (b, sq, h) or k2.shape[:2] != (b, sk) or k2.shape[3] != d2
            or k.shape[2] % k2.shape[2]):
        raise ValueError(
            f"second = (q2 (b, sq, h, d2), k2 (b, sk, h2, d2)) with h2 dividing the "
            f"kv heads ({k.shape[2]}); got {q2.shape} / {k2.shape}")
    ok = (bshd_kernel_ok(sq, sk, h, d, q.dtype) and dv % 128 == 0 and d2 % 64 == 0
          and _k.bshd_two_width_fits(sq, sk, d, dv, d2, q.dtype.itemsize))
    if (impl == "auto" and sk < flash_auto_crossover(d)
            and not _backend.interpret_forced()):
        impl = "xla"
    use_pallas = _backend.choose_impl(impl, ok) == "pallas"
    s_scale = float(scale if scale is not None else 1.0 / (d + d2) ** 0.5)
    return _flash_core_two_width(q, k, v, q2, k2, s_scale, causal, use_pallas)


# --- fused projection + attention block ---------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12))
def fused_qkv_attention(x, w_qkv, b_qkv, w_out, bias, dropout_seed,
                        kv_lens, h, h_kv, d, scale, causal,
                        dropout_rate=0.0):
    """Packed-QKV projection → flash attention → output projection as ONE
    differentiable block in which every large contraction is a plain 2D
    GEMM over (tokens, features) folded views, and the flash kernels read
    q/k/v straight out of the packed projection buffer via window-offset
    index maps.

    Why it exists (PERF.md r3): composed from separate einsums, XLA's
    layout assignment inserts ~4.5 GB/step of conversion copies between
    the projection dots (whose multi-dim-contraction forward/transpose
    lowerings pick non-default layouts) and the Pallas kernels (which pin
    default layouts). Folding everything to 2D GEMMs leaves no layout
    freedom anywhere, and the hand-written VJP contracts dq/dk/dv against
    their weight windows separately — a packed dqkv is never materialized.

    ``x`` (b, s, H); ``w_qkv`` ((h + 2·h_kv)·d, H) packed q|k|v (heads
    contiguous per part); ``b_qkv`` ((h+2·h_kv)·d,); ``w_out`` (O, h·d).
    Returns (b, s, O) — the output-projection bias and (under tp) the
    partial-product reduce stay with the caller, matching
    ``RowParallelLinear``'s post-reduce bias order. Pallas-only (the
    caller gates on kernel eligibility). ``dropout_rate > 0`` applies
    in-kernel probs dropout (``dropout_seed`` required — pass None
    otherwise); masks regenerate in backward from the same counter hash
    (see ``pallas.attention.dropout_keep``). ``kv_lens`` (b,) int32 masks
    each batch row's kv positions >= its length (padded batches; pass
    None for full sequences). ``bias`` (hb, s, s) with hb | h: additive
    score bias read in-kernel (q-head row t reads bias row t % hb),
    differentiated (dbias = Σ_batch dS via the batch-innermost dbias
    kernel); pass None for unbiased attention.

    Backward: without a bias the attention gradients come from ONE kernel
    (``flash_bwd_packed_fused``: every score tile computed once; dk/dv
    summed over the kv group in fp32 VMEM and handed back at kv width, so
    the dk/dv GEMMs below contract (tokens, h_kv·d) operands); lengths and
    dropout ride it. A bias takes the dq/dkv/dbias split. The choice is
    made at trace time from shapes — see ``pallas.attention.
    flash_bwd_packed``.

    Heads of 64 (:func:`packed_kernel_ok`'s pair rule: an even number,
    ``h == h_kv``) ride the same block two heads to a 128-lane tile —
    ``flash_fwd_packed_pair`` / ``flash_bwd_packed_pair_fused``, the same
    mathematics once a head of the pair; lengths and dropout ride them, a
    ``bias`` does not (ValueError)."""
    y, _ = _fused_attn_fwd(x, w_qkv, b_qkv, w_out, bias, dropout_seed,
                           kv_lens, h, h_kv, d, scale, causal, dropout_rate)
    return y


# Heads of 64 in pairs: jitted, so the layers of a model share one traced and
# lowered program a kernel (the pair bodies hold a tile once a head, and a
# kernel body is traced at every call site). The calls at heads of 128 stay
# the plain functions: their programs lower to the text they always did. The
# wrappers' names hold no part a trace reader matches (``flash_``): XLA names
# what it derives from a call after the jitted function.
_PACKED_STATIC = ("scale", "causal", "full_lse", "interpret", "dropout_rate")


@functools.partial(jax.jit, static_argnums=(1, 2, 3), static_argnames=_PACKED_STATIC)
def _pair_forward(qkv, h, h_kv, d, **kw):
    return _k.flash_fwd_packed(qkv, h, h_kv, d, **kw)


@functools.partial(jax.jit, static_argnums=(1, 2, 3), static_argnames=_PACKED_STATIC)
def _pair_backward(qkv, h, h_kv, d, o, lse, do, **kw):
    return _k.flash_bwd_packed(qkv, h, h_kv, d, o, lse, do, **kw)


def _fused_attn_fwd(x, w_qkv, b_qkv, w_out, bias, dropout_seed, kv_lens, h,
                    h_kv, d, scale, causal, dropout_rate=0.0):
    b, s, H = x.shape
    if isinstance(bias, BucketedBias):
        raise ValueError(
            "fused_qkv_attention takes a materialized (hb, s, s) bias; "
            "the bucketed form rides flash_attention(layout='bshd') (same "
            "kernels, separate projections)")
    if bias is not None:
        # same contract flash_attention enforces: a non-dividing hb would
        # pair heads with bias rows inconsistently across batches (the
        # kernels' t % hb map) and the dbias grid would silently drop rows
        if (bias.ndim != 3 or bias.shape[1:] != (s, s)
                or h % bias.shape[0]):
            raise ValueError(
                f"bias must be (hb, {s}, {s}) with hb dividing h ({h}); "
                f"got {bias.shape}")
    qkv = (jnp.dot(x.reshape(-1, H), w_qkv.T) + b_qkv).reshape(b, s, -1)
    # full_lse: keep the (b, h, s, LANES) lane carrier as the residual —
    # backward hands it straight back to the kernel (slicing lane 0 here
    # would force a re-broadcast there, one slice+broadcast pair per layer)
    fwd = _pair_forward if _k.packed_pair(h, h_kv, d) else _k.flash_fwd_packed
    o, lse = fwd(
        qkv, h, h_kv, d, scale=scale, causal=causal, kv_lens=kv_lens,
        bias=bias, full_lse=True, interpret=_backend.interpret_mode(),
        dropout_rate=dropout_rate, dropout_seed=dropout_seed)
    # dead rows (kv_lens == 0): the kernel writes zero context rows and
    # zeros propagate through the projection — no extra masking needed
    y = jnp.dot(o.reshape(-1, h * d), w_out.T).reshape(b, s, -1)
    return y, (x, qkv, o, lse, w_qkv, w_out, bias, dropout_seed, kv_lens)


def _fused_attn_bwd(h, h_kv, d, scale, causal, dropout_rate, res, dy):
    x, qkv, o, lse, w_qkv, w_out, bias, dropout_seed, kv_lens = res
    b, s, H = x.shape
    T = b * s
    dy2 = dy.reshape(T, -1)
    o2 = o.reshape(T, h * d)
    dw_out = jnp.dot(dy2.T, o2)
    do = jnp.dot(dy2, w_out).reshape(b, s, h * d)
    bwd = _pair_backward if _k.packed_pair(h, h_kv, d) else _k.flash_bwd_packed
    out = bwd(
        qkv, h, h_kv, d, o, lse, do, scale=scale, causal=causal,
        kv_lens=kv_lens, bias=bias, interpret=_backend.interpret_mode(),
        dropout_rate=dropout_rate, dropout_seed=dropout_seed)
    dq, dk, dv = out[:3]
    dbias = out[3].astype(bias.dtype) if bias is not None else None
    x2 = x.reshape(T, H)
    dq2 = dq.reshape(T, -1)
    dk2 = dk.reshape(T, -1)
    dv2 = dv.reshape(T, -1)
    wq = w_qkv[:h * d]
    wk = w_qkv[h * d:(h + h_kv) * d]
    wv = w_qkv[(h + h_kv) * d:]
    dx = (jnp.dot(dq2, wq) + jnp.dot(dk2, wk) + jnp.dot(dv2, wv)
          ).reshape(b, s, H)
    dw_qkv = jnp.concatenate(
        [jnp.dot(dq2.T, x2), jnp.dot(dk2.T, x2), jnp.dot(dv2.T, x2)], 0)
    db_qkv = jnp.concatenate(
        [jnp.sum(dq2, 0), jnp.sum(dk2, 0), jnp.sum(dv2, 0)])
    return dx, dw_qkv.astype(w_qkv.dtype), db_qkv.astype(w_qkv.dtype), \
        dw_out.astype(w_out.dtype), dbias, _float0_like(dropout_seed), \
        _float0_like(kv_lens)


fused_qkv_attention.defvjp(_fused_attn_fwd, _fused_attn_bwd)


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, causal: bool = False, scale: Optional[float] = None,
    kv_lens: Optional[jax.Array] = None, bias: Optional[jax.Array] = None,
    impl: str = "auto", layout: str = "bhsd", dropout_rate: float = 0.0,
    dropout_seed: Optional[jax.Array] = None, window: Optional[int] = None,
    second: Optional[tuple] = None,
) -> jax.Array:
    """Blockwise attention over (..., seq, head_dim) with any number of
    leading batch/head dims. No sequence-length cap (cf. fmha's 512).
    HALF-class under O1 (attention is matmul-shaped; the in-kernel softmax
    accumulates fp32 regardless).

    Grouped-query / multi-query attention: k/v may carry FEWER heads than q
    — flattened leading dims must divide q's (e.g. q (b, 8, s, d) with kv
    (b, 2, s, d) is a group of 4; kv (b, 1, s, d) is MQA). The kernel reads
    each kv row once per group via its BlockSpec index map — kv is never
    repeated in HBM. A capability the reference's fixed-shape fmha kernels
    (seq≤512, equal heads) cannot express.

    ``kv_lens``: per-row valid kv length over q's leading dims (padded
    batches) — positions >= the length are masked out; the compute of KV
    blocks entirely past it is skipped dynamically in-kernel (their
    HBM→VMEM copies still run — BlockSpec DMA is unconditional), so ragged
    batches save MXU time but not block DMA. Rows with length 0 return
    zeros. Composes with ``causal``. Passing ``kv_lens=None`` compiles
    kernels with no varlen operand or masking at all. (The reference's
    fused softmax takes a full (b,1,sq,sk) mask tensor; a length vector
    expresses the padded-batch case in O(rows) and keeps the flash memory
    profile.)

    ``impl='auto'`` picks the Pallas kernel from seq >= 1024, or from
    seq >= 512 when head_dim >= 128 (full MXU lanes lower the kernel's
    break-even): below the crossover the grid/launch overhead outweighs the
    saved score-tensor HBM traffic and XLA's batched-matmul composition of
    the same math (still recompute-in-backward via this function's
    custom_vjp — O(s) residuals) is faster on v5e-class chips. Measured
    end-to-end on GPT-medium train steps (v5e): d=64 S=1024 pallas 248.7
    vs xla 264.6 ms/step; d=128 S=512 163.4 vs 170.1 (kernel wins), S=256
    165.8 vs 158.7 (xla wins) — measured before PR 1 on an earlier
    installation, not re-measured on this code. The full-step measurement
    (where the kernel competes with everything else for HBM) is the one
    that matters, not an isolated-kernel timing.

    Where a call lands, by layout and head width (``impl='auto'``, bf16 or
    fp32, sequences in whole 128-blocks at or past the crossover):

    =====================  ==========================  =========================
    entry                  heads of 128 (and 256)      heads of 64
    =====================  ==========================  =========================
    ``layout='bhsd'``      flat kernels                flat kernels
    ``layout='bshd'``      seq-major kernels           one head: seq-major;
                                                       several: XLA (explicit
                                                       ``'pallas'`` raises)
    ``fused_qkv_attention``  packed kernels            an even ``h == h_kv``:
    (``GPTModel``, by                                  packed PAIR kernels, two
    ``packed_kernel_ok``)                              heads a lane tile; else
                                                       ``GPTModel`` keeps the
                                                       flat kernels
    =====================  ==========================  =========================

    ``layout='bshd'``: operands are (batch, seq, heads, head_dim) — the
    seq-major layout the QKV projection GEMMs naturally emit. The Pallas
    kernels read it via head-strided index maps, so NO layout-conversion
    copies sit between the projections and the kernels (the bh-flat layout
    cost the flagship ~4.5 GB/step of pure copies — PERF.md r3). Prefer it
    whenever q/k/v come straight from a (tokens, features) GEMM. In this
    layout ``kv_lens`` is PER BATCH ((b,) int32 — heads share a row's
    padding), which is both the padded-batch reality and what the
    kernels' head-folded index maps consume with zero expansion.

    ``dropout_rate > 0`` applies IN-KERNEL probs dropout (the reference's
    fused-attention capability, ``apex/contrib/csrc/fmha/fmha_api.cpp:44``):
    masks come from a stateless counter hash of (seed, head, row, col) —
    O(block) memory, regenerated in backward, bit-identical between the
    Pallas and XLA dispatches, deterministic per ``dropout_seed`` (int32
    scalar, required). The softmax normalizer is computed pre-dropout
    (standard probs-dropout semantics: E[output] = no-dropout output).
    The realized drop probability is ``dropout_rate`` quantized to the
    nearest multiple of 2^-24 (the hash compares in a 24-bit integer
    domain) — sub-1e-7 rates round to off.

    ``bias`` (hb, sq, sk): an arbitrary ADDITIVE score bias applied
    IN-KERNEL — the reference's fused-mask capability
    (``csrc/megatron/scaled_masked_softmax.cpp:85-94`` applies a
    per-batch mask fused with scale+softmax; the additive ``attn_mask``
    variants of ``contrib/multihead_attn/self_multihead_attn.py:144-198``)
    generalized: T5 relative position bias, ALiBi slopes, additive
    attention masks all ride the same operand. Row ``r`` of the flattened
    (batch·heads) leading dims reads bias row ``r % hb`` — so (h, sq, sk)
    is a per-head bias shared over batch (the T5 case), (1, sq, sk) a
    broadcast bias, (b·h, sq, sk) fully per-row. Added to the scaled
    scores BEFORE causal/kv_lens masks; differentiable (dbias = Σ over
    the sharing rows of dS, computed by a third, batch-innermost backward
    kernel — ~2 extra GEMM passes, paid only when bias is given).
    Composes with causal, kv_lens, dropout, GQA, and both layouts (with
    ``layout='bshd'`` hb must divide h).

    ``window`` (static int; needs ``causal=True``, ``layout='bshd'`` and no
    ``bias``): sliding-window attention — query ``i`` sees keys
    ``i - window < j <= i`` (positions bottom-right aligned as for causal).
    The kernels (named ``flash_fwd_bshd_win`` and, the backward in one
    pass, ``flash_bwd_bshd_win_fused``; with a longer key sequence than
    query sequence the dq/dkv split ``flash_bwd_bshd_win_dq`` / ``_dkv``)
    walk only the blocks a q block's band touches: a tile wholly
    outside the band is neither fetched nor computed, tiles cut by either
    edge are masked. ``impl='xla'`` masks the materialised scores.

    The backward of ``layout='bshd'`` is one kernel wherever the operands
    allow it (``flash_bwd_bshd_fused``: no ``bias``, ``sq == sk``, and the
    (s, d) fp32 dk/dv accumulators inside the VMEM a kernel may ask for —
    the rule of :func:`apex_tpu.ops.pallas.attention.flash_bwd_bshd`):
    every score tile computed once, dk/dv summed over the kv group in VMEM.
    Nothing here chooses it.

    Two head widths (``layout='bshd'``; no ``bias``, ``kv_lens``, dropout or
    ``window``): ``v`` (b, sk, h_kv, dv) may be wider or narrower than q and
    k, and the output is (b, sq, h, dv). ``second = (q2 (b, sq, h, d2),
    k2 (b, sk, h2, d2))`` adds a second score term, ``(q·kᵀ + q2·k2ᵀ) *
    scale`` (default scale ``(d + d2) ** -0.5``), whose key may have fewer
    heads than k (h2 | h_kv; one in latent attention, where q2/k2 are the
    rotary features and k2 is shared by every head): the kernels
    (``flash_fwd_bshd_mla``, ``flash_bwd_bshd_mla_fused``) read k2 by index
    map, never repeated in HBM, pad d2 = 64 to a lane tile in VMEM only, and
    sum dk2 over all its q heads in VMEM. Such a call has the one-pass
    backward only: shapes it does not fit (``sq != sk``, accumulators past
    the VMEM cap — about 20 k positions at d = dv = 128, d2 = 64 —, d or dv
    no multiple of 128, d2 no multiple of 64) take the XLA composition,
    which materialises the scores. Plain calls keep their split backward
    for a bias, ``sq != sk`` and sequences past the cap, as above."""
    q, k, v = apply_op_rules("attention", q, k, v)
    if second is not None or (layout == "bshd" and v.ndim == 4
                              and v.shape[-1] != q.shape[-1]):
        if (layout != "bshd" or bias is not None or kv_lens is not None
                or dropout_rate > 0.0 or window is not None):
            raise ValueError(
                "two head widths or a second score term need layout='bshd' "
                "and no bias, kv_lens, dropout or window")
        if second is not None:
            second = apply_op_rules("attention", *second)
        return _two_width_attention(q, k, v, second, scale, causal, impl)
    if window is not None and (not causal or layout != "bshd" or bias is not None
                               or int(window) < 1):
        raise ValueError(
            f"window={window!r} needs causal=True, layout='bshd', no bias "
            f"and window >= 1")
    if layout not in ("bhsd", "bshd"):
        raise ValueError(f"layout must be bhsd|bshd, got {layout!r}")
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got "
                         f"{dropout_rate}")
    if dropout_rate > 0.0:
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires dropout_seed")
        dropout_seed = jnp.asarray(dropout_seed, jnp.int32)
    else:
        dropout_seed = None
    if isinstance(bias, BucketedBias):
        _validate_bucketed(bias)
    elif bias is not None:
        sq_, sk_ = q.shape[-2], k.shape[-2]
        if layout == "bshd":
            sq_, sk_ = q.shape[1], k.shape[1]
        if bias.ndim != 3 or bias.shape[1:] != (sq_, sk_):
            raise ValueError(
                f"bias must be (hb, sq, sk) = (hb, {sq_}, {sk_}); got "
                f"{bias.shape}")
    if layout == "bshd":
        if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
            raise ValueError(
                f"layout='bshd' takes (b, s, h, d) operands; got "
                f"{q.shape} / {k.shape}")
        if causal and q.shape[1] > k.shape[1]:
            raise ValueError(
                f"causal attention requires sq <= sk; got sq={q.shape[1]} "
                f"> sk={k.shape[1]}")
        if q.shape[2] % k.shape[2] or k.shape[:2] != v.shape[:2]:
            raise ValueError(
                f"kv heads ({k.shape[2]}) must divide q heads "
                f"({q.shape[2]}) with matching batch/seq dims")
        d = q.shape[-1]
        s_scale = float(scale if scale is not None else 1.0 / d ** 0.5)
        if kv_lens is not None:
            # per-BATCH lengths (heads share a row's padding) — the (b,)
            # form the kernels' t//h index maps consume directly
            if kv_lens.shape != (q.shape[0],):
                raise ValueError(
                    f"layout='bshd' takes per-batch kv_lens of shape "
                    f"({q.shape[0]},); got {kv_lens.shape}")
            kv_lens = kv_lens.astype(jnp.int32)
        if bias is not None and q.shape[2] % _bias_rows(bias):
            raise ValueError(
                f"layout='bshd' needs bias rows ({_bias_rows(bias)}) "
                f"dividing q heads ({q.shape[2]})")
        ok = bshd_kernel_ok(q.shape[1], k.shape[1], q.shape[2], d, q.dtype)
        impl_ = impl
        if (impl_ == "auto" and k.shape[1] < flash_auto_crossover(d)
                and not _backend.interpret_forced()):
            impl_ = "xla"
        use_pallas = _backend.choose_impl(impl_, ok) == "pallas"
        return _flash_core_bshd(q, k, v, bias, kv_lens, dropout_seed,
                                s_scale, causal, use_pallas, dropout_rate,
                                None if window is None else int(window))
    d = q.shape[-1]
    if causal and q.shape[-2] > k.shape[-2]:
        # bottom-right-aligned causal with sq > sk gives the first
        # (sq - sk) q rows ZERO visible keys — their softmax is undefined
        # (the kernel would emit exp(0)-weighted garbage). No attention
        # semantics wants this; reject instead of returning garbage.
        raise ValueError(
            f"causal attention requires sq <= sk (bottom-right alignment); "
            f"got sq={q.shape[-2]} > sk={k.shape[-2]} — rows before the "
            f"context start would attend nothing")
    scale = float(scale if scale is not None else 1.0 / d ** 0.5)
    lead = q.shape[:-2]
    q3 = q.reshape(-1, q.shape[-2], d)
    k3 = k.reshape(-1, k.shape[-2], d)
    v3 = v.reshape(-1, v.shape[-2], d)
    if k.shape[:-2] != v.shape[:-2]:
        raise ValueError(f"k/v leading dims differ: {k.shape} vs {v.shape}")
    if q.ndim >= 4:
        # batch dims must MATCH; only the head axis (last leading dim) may
        # be narrower on kv — a flattened-ratio check alone would accept a
        # mismatched batch dim and silently pair q rows with wrong batches
        if (q.shape[:-3] != k.shape[:-3]
                or q.shape[-3] % k.shape[-3]):
            raise ValueError(
                f"kv heads ({k.shape[-3]}) must divide q heads "
                f"({q.shape[-3]}) with equal batch dims "
                f"({q.shape[:-3]} vs {k.shape[:-3]}) for grouped-query "
                f"attention")
    elif q3.shape[0] % k3.shape[0]:
        raise ValueError(
            f"kv heads ({k3.shape[0]} flattened) must divide q heads "
            f"({q3.shape[0]} flattened) for grouped-query attention")
    ok = (
        q3.shape[-2] % 128 == 0 and k3.shape[-2] % 128 == 0
        and (d % 128 == 0 or d == 64)
        # the Mosaic dialect has no f16: strict-fp16 runs (half_dtype=
        # float16) take the XLA composition — measured on hardware, see
        # PERF.md "fp16-strict" (bf16 is the TPU half type; fp16 pays
        # this kernel tax on top of its scaler requirement)
        and q.dtype != jnp.float16
    )
    if (impl == "auto" and k3.shape[-2] < flash_auto_crossover(d)
            and not _backend.interpret_forced()):
        impl = "xla"  # grid overhead beats saved score traffic below this
    use_pallas = _backend.choose_impl(impl, ok) == "pallas"
    if kv_lens is not None:
        if kv_lens.shape != lead:
            raise ValueError(
                f"kv_lens shape {kv_lens.shape} must equal q's leading dims "
                f"{lead}")
        # int32 before the custom_vjp: backward returns a float0 cotangent,
        # which JAX only accepts for integer primals
        kv_lens = kv_lens.reshape(-1).astype(jnp.int32)
    if bias is not None and q3.shape[0] % _bias_rows(bias):
        raise ValueError(
            f"bias rows ({_bias_rows(bias)}) must divide q's flattened "
            f"leading dims ({q3.shape[0]})")
    o = _flash_core(q3, k3, v3, bias, kv_lens, dropout_seed, scale, causal,
                    use_pallas, dropout_rate)
    return o.reshape(*lead, q.shape[-2], d)


# --- ring attention (context parallel) ---------------------------------------

def zigzag_indices(cp: int, s: int):
    """The zigzag (striped) sequence permutation for causal context
    parallelism: the sequence is cut into ``2·cp`` stripes and device ``r``
    holds stripes ``(r, 2cp−1−r)`` — pairing an early stripe (little causal
    work) with a late one (much) so every rank's total is equal. Returns
    ``order`` such that ``x[order]`` laid out contiguously and sharded over
    ``cp`` gives each device its stripe pair, plus the inverse."""
    import numpy as np
    if s % (2 * cp):
        raise ValueError(f"sequence ({s}) must divide into 2*cp ({2 * cp}) "
                         "stripes for zigzag sharding")
    stripe = s // (2 * cp)
    order = np.concatenate([
        np.r_[r * stripe:(r + 1) * stripe,
              (2 * cp - 1 - r) * stripe:(2 * cp - r) * stripe]
        for r in range(cp)
    ])
    inverse = np.argsort(order)
    return order, inverse


def zigzag_shard(x: jax.Array, cp: int, seq_axis: int = -2) -> jax.Array:
    """Permute ``seq_axis`` into zigzag order (host/global side; shard the
    result contiguously over the cp mesh axis)."""
    order, _ = zigzag_indices(cp, x.shape[seq_axis])
    return jnp.take(x, jnp.asarray(order), axis=seq_axis)


def zigzag_unshard(x: jax.Array, cp: int, seq_axis: int = -2) -> jax.Array:
    """Inverse of :func:`zigzag_shard`."""
    _, inverse = zigzag_indices(cp, x.shape[seq_axis])
    return jnp.take(x, jnp.asarray(inverse), axis=seq_axis)


def seed_from_key(key) -> jax.Array:
    """The int32 dropout seed for the counter-hash dropout family from a
    ``jax.random`` PRNG key. One place so every module (GPT blocks,
    contrib MHA, ...) derives seeds identically — the mapping is the
    cross-module determinism contract for the in-kernel masks."""
    return jax.lax.bitcast_convert_type(
        jax.random.bits(key, (), jnp.uint32), jnp.int32)


def fold_dropout_seed(seed, *ids):
    """Derive a decorrelated int32 dropout seed from ``seed`` and integer
    identifiers (cp rank, ring step, piece index, ...) via the same fmix32
    avalanche the mask hash uses. Deterministic, traced-friendly; the
    tool that lets distributed attention give every (shard, step, piece)
    its own mask stream while forward and backward re-derive identical
    seeds."""
    h = jnp.asarray(seed).astype(jnp.uint32)
    for i in ids:
        h = _k._fmix32(h ^ (jnp.asarray(i).astype(jnp.uint32)
                            * jnp.uint32(0x9E3779B9)))
    return jax.lax.bitcast_convert_type(h, jnp.int32)


def _piece_seed(dropout_seed, rank, t, piece):
    """The ring's per-(rank, step, piece) mask-stream fold — ONE
    definition so forward and the hand-written backward can never drift
    apart (bit-identical folds are the gradient-correctness contract)."""
    if dropout_seed is None:
        return None
    return fold_dropout_seed(dropout_seed, rank, t, piece)


def _piece_fwd(q, k, v, scale, causal, use_pallas, dropout_rate=0.0,
               dropout_seed=None, kv_lens=None, bias=None):
    """(o, lse) of one attention piece through the flash kernel (or the XLA
    composition below its crossover). ``kv_lens``/``bias`` are this
    PIECE's window-local operands (lengths clipped to the piece's kv
    window; a :class:`BucketedBias` with the piece's global offsets).
    Rows whose window is EMPTY come back with lse == NEG_INF — the
    single-kernel dead-row lse=0 is an *output* convention; inside the
    ring's online-softmax fold it would weight a dead piece e^0."""
    o, res = _flash_fwd_res(q, k, v, bias, kv_lens, dropout_seed, scale,
                            causal, use_pallas, dropout_rate)
    lse = res[4]
    if lse.ndim == 3:  # pallas (bh, s, LANES) carrier → (bh, s) rows
        lse = lse[..., 0]
    if kv_lens is not None:
        lse = jnp.where(kv_lens[:, None] > 0, lse, _k.NEG_INF)
    return o, lse


def _piece_fwd_bshd(q, k, v, scale, causal, use_pallas, dropout_rate=0.0,
                    dropout_seed=None, kv_lens=None, bias=None):
    """(o (b, s, h, d), lse (b, h, s)) of one seq-major piece — the
    bshd-layout twin of :func:`_piece_fwd` (kernels read the projection
    GEMMs' natural layout; no transpose round trip per ring step).
    ``kv_lens`` is the piece-window (b,) form; dead-piece rows get
    lse == NEG_INF (see :func:`_piece_fwd`)."""
    o, res = _flash_fwd_res_bshd(q, k, v, bias, kv_lens, dropout_seed,
                                 scale, causal, use_pallas, dropout_rate)
    lse = res[4]
    # the pallas path returns the (b, h, s, LANES) carrier; the ring's
    # fold arithmetic runs on the sliced (b, h, s) row form
    lse = lse[..., 0] if lse.ndim == 4 else lse
    if kv_lens is not None:
        lse = jnp.where(kv_lens[:, None, None] > 0, lse, _k.NEG_INF)
    return o, lse


def _fold(o1, l1, o2, l2, bshd=False):
    """Merge two normalized attention pieces over the same q rows:
    (o, lse) ⊕ (o, lse) → (o, lse), the online-softmax combine. With
    ``bshd``, o is (b, s, h, d) and lse (b, h, s) — the weights transpose
    to the seq-major broadcast."""
    m = jnp.maximum(l1, l2)
    e1 = jnp.exp(l1 - m)
    e2 = jnp.exp(l2 - m)
    tot = e1 + e2
    w1, w2 = e1 / tot, e2 / tot
    if bshd:
        w1 = w1.transpose(0, 2, 1)[..., None]
        w2 = w2.transpose(0, 2, 1)[..., None]
    else:
        w1, w2 = w1[..., None], w2[..., None]
    o = o1 * w1 + o2.astype(jnp.float32) * w2
    return o, m + jnp.log(tot)


def _piece_lens(kv_lens, k_off, extent):
    """This piece's kv window lengths: global valid lengths clipped to a
    kv window starting at global position ``k_off`` with ``extent``
    columns — how the per-row/per-batch ``kv_lens`` operand rides any
    sequence sharding (a position is valid iff its GLOBAL index is below
    the row's length)."""
    if kv_lens is None:
        return None
    return jnp.clip(kv_lens - k_off, 0, extent)


def _zigzag_pair_lens(kv_lens, a_off, b_off, ss):
    """Valid kv count of the CONCATENATED zigzag stripe pair [a; b]: the
    pair is position-monotonic (a < b), so the globally-valid positions
    form a local PREFIX and a single per-row length expresses them."""
    if kv_lens is None:
        return None
    return (jnp.clip(kv_lens - a_off, 0, ss)
            + jnp.clip(kv_lens - b_off, 0, ss))


def _ring_fwd_impl(q, k, v, axis_name, scale, causal, use_pallas,
                   dropout_rate=0.0, dropout_seed=None, bshd=False,
                   kv_lens=None, bias=None):
    """Layout-generic ring forward: ``bshd=False`` takes (bh, s, d)
    operands with lse (bh, s); ``bshd=True`` takes (b, s, h, d) with lse
    (b, h, s) — the seq axis is 1 either way, only the lse carrier and
    the piece/fold functions differ (the bshd kernels read the projection
    GEMMs' layout directly, removing the per-ring-step transpose round
    trip the flat layout paid).

    ``kv_lens`` (global per-row/per-batch valid lengths) and ``bias`` (a
    :class:`BucketedBias`) ride per piece: every piece knows its kv
    window's GLOBAL start, so lengths clip to the window
    (:func:`_piece_lens`) and the bias recomputes in-kernel from the
    window's offsets (:meth:`BucketedBias.shifted`). With bias under
    causal zigzag, step 0 decomposes into its three stripe pieces
    (lo·lo causal, hi·hi causal, hi·lo full) — the concatenated pair is
    position-monotonic but not position-CONTIGUOUS, which a mask
    tolerates and an offset-pair does not."""
    cp = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % cp) for i in range(cp)]
    piece = _piece_fwd_bshd if bshd else _piece_fwd
    lse_ax = 2 if bshd else 1
    s_loc = q.shape[1]

    def pseed(t, piece_id):
        # each (q, k) pair is covered by exactly one piece, so the
        # per-piece streams stay i.i.d. Bernoulli globally
        return _piece_seed(dropout_seed, rank, t, piece_id)

    def pb(q_off, k_off):
        return None if bias is None else bias.shifted(q_off, k_off)

    def rotate(t):
        return jax.tree.map(
            lambda x: jax.lax.ppermute(x, axis_name, perm), t)

    def pin_dead(lse):
        # GLOBALLY-dead rows (kv_lens == 0): every piece folded in at
        # lse == NEG_INF, so the accumulated lse is ~NEG_INF — pin it to
        # 0 (the single-kernel dead-row convention) AFTER all folds, so
        # backward's p = exp(NEG_INF − 0) underflows to 0 on every piece
        # (with lse ≈ NEG_INF it would be exp(0) = 1: garbage dq/dk/dv
        # for padded-out rows)
        if kv_lens is None:
            return lse
        live = kv_lens > 0
        return jnp.where(live[:, None, None] if bshd else live[:, None],
                         lse, 0.0)

    if not causal:
        # contiguous sharding: shard r holds global rows [r·s_loc, ...)
        q_off = rank * s_loc
        o0, l0 = piece(q, k, v, scale, False, use_pallas,
                       dropout_rate, pseed(0, 0),
                       kv_lens=_piece_lens(kv_lens, q_off, s_loc),
                       bias=pb(q_off, q_off))

        def step(carry, t):
            o_acc, l_acc, kv = carry
            kv = rotate(kv)
            k_off = ((rank - t) % cp) * s_loc
            oi, li = piece(q, kv[0], kv[1], scale, False, use_pallas,
                           dropout_rate, pseed(t, 0),
                           kv_lens=_piece_lens(kv_lens, k_off, s_loc),
                           bias=pb(q_off, k_off))
            o_acc, l_acc = _fold(o_acc, l_acc, oi, li, bshd)
            return (o_acc, l_acc, kv), None

        (o_acc, l_acc, _), _ = jax.lax.scan(
            step, (o0.astype(jnp.float32), l0, (k, v)),
            jnp.arange(1, cp), length=cp - 1)
        return o_acc.astype(q.dtype), pin_dead(l_acc)

    ss = s_loc // 2
    # zigzag stripe pair: rank r holds stripes (r, 2cp−1−r) of 2·cp
    a_off = rank * ss
    b_off = (2 * cp - 1 - rank) * ss
    lhalf = lambda l: (jax.lax.slice_in_dim(l, 0, ss, axis=lse_ax),  # noqa: E731
                       jax.lax.slice_in_dim(l, ss, 2 * ss, axis=lse_ax))
    q_lo, q_hi = q[:, :ss], q[:, ss:]

    # step 0 — the local stripe pair. Without bias: ONE causal flash over
    # the position-monotonic pair (local causal == global causal; varlen
    # valid positions form a local prefix, _zigzag_pair_lens). With bias:
    # the three stripe pieces, each position-contiguous with its own
    # global offsets.
    if bias is None:
        o0, l0 = piece(q, k, v, scale, True, use_pallas,
                       dropout_rate, pseed(0, 0),
                       kv_lens=_zigzag_pair_lens(kv_lens, a_off, b_off, ss))
        l0_lo, l0_hi = lhalf(l0)
        o_lo0, l_lo0 = o0[:, :ss].astype(jnp.float32), l0_lo
        o_hi0, l_hi0 = o0[:, ss:].astype(jnp.float32), l0_hi
    else:
        k_lo0, k_hi0 = k[:, :ss], k[:, ss:]
        v_lo0, v_hi0 = v[:, :ss], v[:, ss:]
        o_ll, l_ll = piece(q_lo, k_lo0, v_lo0, scale, True, use_pallas,
                           dropout_rate, pseed(0, 0),
                           kv_lens=_piece_lens(kv_lens, a_off, ss),
                           bias=pb(a_off, a_off))
        o_hh, l_hh = piece(q_hi, k_hi0, v_hi0, scale, True, use_pallas,
                           dropout_rate, pseed(0, 1),
                           kv_lens=_piece_lens(kv_lens, b_off, ss),
                           bias=pb(b_off, b_off))
        o_hl, l_hl = piece(q_hi, k_lo0, v_lo0, scale, False, use_pallas,
                           dropout_rate, pseed(0, 2),
                           kv_lens=_piece_lens(kv_lens, a_off, ss),
                           bias=pb(b_off, a_off))
        o_lo0, l_lo0 = o_ll.astype(jnp.float32), l_ll
        o_hi0, l_hi0 = _fold(o_hh, l_hh, o_hl, l_hl, bshd)

    def step(carry, t):
        o_lo, l_lo, o_hi, l_hi, kv = carry
        kv = rotate(kv)
        kk, vv = kv
        k_lo, k_hi = kk[:, :ss], kk[:, ss:]
        v_lo, v_hi = vv[:, :ss], vv[:, ss:]
        j = (rank - t) % cp
        ja, jb = j * ss, (2 * cp - 1 - j) * ss
        # piece 1: this rank's HIGH stripe vs the arriving LOW stripe —
        # always a full (unmasked) attend (stripe j < cp <= 2cp−1−rank)
        o1, l1 = piece(q_hi, k_lo, v_lo, scale, False, use_pallas,
                       dropout_rate, pseed(t, 1),
                       kv_lens=_piece_lens(kv_lens, ja, ss),
                       bias=pb(b_off, ja))
        o_hi, l_hi = _fold(o_hi, l_hi, o1, l1, bshd)
        # piece 2: j < rank → our LOW stripe sees their LOW stripe;
        # j > rank → our HIGH stripe sees their HIGH stripe. Both full
        # attends — zigzag leaves no partially- or fully-masked work.
        lo_case = j < rank
        q2 = jnp.where(lo_case, q_lo, q_hi)
        k2 = jnp.where(lo_case, k_lo, k_hi)
        v2 = jnp.where(lo_case, v_lo, v_hi)
        qo2 = jnp.where(lo_case, a_off, b_off)
        ko2 = jnp.where(lo_case, ja, jb)
        o2, l2 = piece(q2, k2, v2, scale, False, use_pallas,
                       dropout_rate, pseed(t, 2),
                       kv_lens=_piece_lens(kv_lens, ko2, ss),
                       bias=pb(qo2, ko2))
        o_lo2, l_lo2 = _fold(o_lo, l_lo, o2, l2, bshd)
        o_hi2, l_hi2 = _fold(o_hi, l_hi, o2, l2, bshd)
        o_lo = jnp.where(lo_case, o_lo2, o_lo)
        l_lo = jnp.where(lo_case, l_lo2, l_lo)
        o_hi = jnp.where(lo_case, o_hi, o_hi2)
        l_hi = jnp.where(lo_case, l_hi, l_hi2)
        return (o_lo, l_lo, o_hi, l_hi, kv), None

    init = (o_lo0, l_lo0, o_hi0, l_hi0, (k, v))
    (o_lo, l_lo, o_hi, l_hi, _), _ = jax.lax.scan(
        step, init, jnp.arange(1, cp), length=cp - 1)
    o = jnp.concatenate([o_lo, o_hi], axis=1).astype(q.dtype)
    lse = jnp.concatenate([l_lo, l_hi], axis=lse_ax)
    return o, pin_dead(lse)


def _ring_bwd_impl(q, k, v, o, lse, do, axis_name, scale, causal,
                   use_pallas, dropout_rate=0.0, dropout_seed=None,
                   bshd=False, kv_lens=None, bias=None):
    """The distributed flash backward: per ring step call ``flash_bwd``
    with the GLOBAL (o, lse) — p and Δ are then exact per shard — while a
    dkv accumulator travels the ring with its kv shard and arrives home
    after a full cycle carrying every rank's contribution (the reference
    has no CP at all; this is the standard ring-attention backward).
    Dropout: each piece re-derives the SAME (rank, step, piece) seed fold
    as forward, so masks regenerate exactly. ``kv_lens``/``bias``: each
    piece re-derives the SAME window lens/offsets as forward; the
    bucket-table cotangent accumulates across pieces into a FOURTH return
    (fp32, this rank's partial — the caller psums it over the cp axis:
    the global dS decomposes disjointly over (rank, step, piece)).
    Returns (dq, dk, dv, dtable-or-None)."""
    cp = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % cp) for i in range(cp)]
    lse_ax = 2 if bshd else 1
    s_loc = q.shape[1]

    def piece_bwd(qq, kk, vv, oo, ll, ddo, caus, sd, lens=None, pbias=None):
        # both layouts return the RAW fp32 bucket-table grad (no cast to
        # the table dtype between pieces — the cp·3 partials accumulate
        # full-precision, matching the single-chip cast-once-at-the-end)
        impl = _flash_bwd_bshd_impl if bshd else _flash_bwd_impl
        return impl(qq, kk, vv, oo, ll, ddo, lens, scale, caus,
                    use_pallas, dropout_rate, sd, pbias)

    def pseed(t, piece):
        return _piece_seed(dropout_seed, rank, t, piece)

    def pb(q_off, k_off):
        return None if bias is None else bias.shifted(q_off, k_off)

    def rotate_tree(t):
        return jax.tree.map(
            lambda x: jax.lax.ppermute(x, axis_name, perm), t)

    # dtable accumulator: the bias-less ring carries a scalar dummy so the
    # scan carry structure stays uniform (dead weight of one float)
    dt0 = (jnp.zeros(bias.table.shape, jnp.float32) if bias is not None
           else jnp.zeros((), jnp.float32))

    def dt_add(acc, dbi):
        return acc if dbi is None else acc + dbi.astype(jnp.float32)

    if not causal:
        q_off = rank * s_loc
        dq0, dk0, dv0, db0 = piece_bwd(
            q, k, v, o, lse, do, False, pseed(0, 0),
            lens=_piece_lens(kv_lens, q_off, s_loc), pbias=pb(q_off, q_off))
        dt0 = dt_add(dt0, db0)

        def step(carry, t):
            dq, kv, dk, dv, dt = carry
            kv, (dk, dv) = rotate_tree(kv), rotate_tree((dk, dv))
            k_off = ((rank - t) % cp) * s_loc
            dqi, dki, dvi, dbi = piece_bwd(
                q, kv[0], kv[1], o, lse, do, False, pseed(t, 0),
                lens=_piece_lens(kv_lens, k_off, s_loc),
                pbias=pb(q_off, k_off))
            return (dq + dqi, kv, dk + dki.astype(dk.dtype),
                    dv + dvi.astype(dv.dtype), dt_add(dt, dbi)), None

        init = (dq0.astype(jnp.float32), (k, v),
                dk0.astype(jnp.float32), dv0.astype(jnp.float32), dt0)
        (dq, _, dk, dv, dt), _ = jax.lax.scan(step, init, jnp.arange(1, cp),
                                              length=cp - 1)
        dk, dv = rotate_tree((dk, dv))  # final hop brings accumulators home
        return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
                dt if bias is not None else None)

    ss = s_loc // 2
    a_off = rank * ss
    b_off = (2 * cp - 1 - rank) * ss
    halves = lambda x: (x[:, :ss], x[:, ss:])
    lhalf = lambda l: (jax.lax.slice_in_dim(l, 0, ss, axis=lse_ax),  # noqa: E731
                       jax.lax.slice_in_dim(l, ss, 2 * ss, axis=lse_ax))
    q_lo, q_hi = halves(q)
    o_lo, o_hi = halves(o)
    l_lo, l_hi = lhalf(lse)
    do_lo, do_hi = halves(do)
    f32 = jnp.float32

    if bias is None:
        dq0, dk0, dv0, _ = piece_bwd(
            q, k, v, o, lse, do, True, pseed(0, 0),
            lens=_zigzag_pair_lens(kv_lens, a_off, b_off, ss))
        dq_lo0, dq_hi0 = dq0[:, :ss].astype(f32), dq0[:, ss:].astype(f32)
        dk_lo0, dk_hi0 = dk0[:, :ss].astype(f32), dk0[:, ss:].astype(f32)
        dv_lo0, dv_hi0 = dv0[:, :ss].astype(f32), dv0[:, ss:].astype(f32)
    else:
        # the forward's three stripe pieces, mirrored (same seeds/windows)
        k_lo0, k_hi0 = halves(k)
        v_lo0, v_hi0 = halves(v)
        dqll, dkll, dvll, dbll = piece_bwd(
            q_lo, k_lo0, v_lo0, o_lo, l_lo, do_lo, True, pseed(0, 0),
            lens=_piece_lens(kv_lens, a_off, ss), pbias=pb(a_off, a_off))
        dqhh, dkhh, dvhh, dbhh = piece_bwd(
            q_hi, k_hi0, v_hi0, o_hi, l_hi, do_hi, True, pseed(0, 1),
            lens=_piece_lens(kv_lens, b_off, ss), pbias=pb(b_off, b_off))
        dqhl, dkhl, dvhl, dbhl = piece_bwd(
            q_hi, k_lo0, v_lo0, o_hi, l_hi, do_hi, False, pseed(0, 2),
            lens=_piece_lens(kv_lens, a_off, ss), pbias=pb(b_off, a_off))
        dq_lo0 = dqll.astype(f32)
        dq_hi0 = dqhh.astype(f32) + dqhl.astype(f32)
        dk_lo0 = dkll.astype(f32) + dkhl.astype(f32)
        dk_hi0 = dkhh.astype(f32)
        dv_lo0 = dvll.astype(f32) + dvhl.astype(f32)
        dv_hi0 = dvhh.astype(f32)
        dt0 = dt_add(dt_add(dt_add(dt0, dbll), dbhh), dbhl)

    def step(carry, t):
        dq_lo, dq_hi, kv, dk_lo, dk_hi, dv_lo, dv_hi, dt = carry
        kv = rotate_tree(kv)
        dk_lo, dk_hi, dv_lo, dv_hi = rotate_tree(
            (dk_lo, dk_hi, dv_lo, dv_hi))
        kk, vv = kv
        k_lo, k_hi = halves(kk)
        v_lo, v_hi = halves(vv)
        j = (rank - t) % cp
        ja, jb = j * ss, (2 * cp - 1 - j) * ss
        # piece 1 (mirror of forward): q_hi vs arriving kv_lo, full attend
        dq1, dk1, dv1, db1 = piece_bwd(
            q_hi, k_lo, v_lo, o_hi, l_hi, do_hi, False, pseed(t, 1),
            lens=_piece_lens(kv_lens, ja, ss), pbias=pb(b_off, ja))
        dq_hi = dq_hi + dq1
        dk_lo = dk_lo + dk1
        dv_lo = dv_lo + dv1
        dt = dt_add(dt, db1)
        # piece 2: the selected stripe pair
        lo_case = j < rank
        q2 = jnp.where(lo_case, q_lo, q_hi)
        o2 = jnp.where(lo_case, o_lo, o_hi)
        l2 = jnp.where(lo_case, l_lo, l_hi)
        do2 = jnp.where(lo_case, do_lo, do_hi)
        k2 = jnp.where(lo_case, k_lo, k_hi)
        v2 = jnp.where(lo_case, v_lo, v_hi)
        qo2 = jnp.where(lo_case, a_off, b_off)
        ko2 = jnp.where(lo_case, ja, jb)
        dq2, dk2, dv2, db2 = piece_bwd(
            q2, k2, v2, o2, l2, do2, False, pseed(t, 2),
            lens=_piece_lens(kv_lens, ko2, ss), pbias=pb(qo2, ko2))
        dq_lo = dq_lo + jnp.where(lo_case, dq2, 0.0)
        dq_hi = dq_hi + jnp.where(lo_case, 0.0, dq2)
        dk_lo = dk_lo + jnp.where(lo_case, dk2, 0.0)
        dk_hi = dk_hi + jnp.where(lo_case, 0.0, dk2)
        dv_lo = dv_lo + jnp.where(lo_case, dv2, 0.0)
        dv_hi = dv_hi + jnp.where(lo_case, 0.0, dv2)
        dt = dt_add(dt, db2)
        return (dq_lo, dq_hi, kv, dk_lo, dk_hi, dv_lo, dv_hi, dt), None

    init = (dq_lo0, dq_hi0, (k, v), dk_lo0, dk_hi0, dv_lo0, dv_hi0, dt0)
    (dq_lo, dq_hi, _, dk_lo, dk_hi, dv_lo, dv_hi, dt), _ = jax.lax.scan(
        step, init, jnp.arange(1, cp), length=cp - 1)
    dk_lo, dk_hi, dv_lo, dv_hi = rotate_tree((dk_lo, dk_hi, dv_lo, dv_hi))
    dq = jnp.concatenate([dq_lo, dq_hi], axis=1).astype(q.dtype)
    dk = jnp.concatenate([dk_lo, dk_hi], axis=1).astype(k.dtype)
    dv = jnp.concatenate([dv_lo, dv_hi], axis=1).astype(v.dtype)
    return dq, dk, dv, (dt if bias is not None else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _ring_core(q, k, v, bias, kv_lens, dropout_seed, axis_name, scale,
               causal, use_pallas, dropout_rate, bshd):
    o, _ = _ring_fwd_impl(q, k, v, axis_name, scale, causal, use_pallas,
                          dropout_rate, dropout_seed, bshd, kv_lens, bias)
    return o


def _ring_fwd(q, k, v, bias, kv_lens, dropout_seed, axis_name, scale,
              causal, use_pallas, dropout_rate, bshd):
    o, lse = _ring_fwd_impl(q, k, v, axis_name, scale, causal, use_pallas,
                            dropout_rate, dropout_seed, bshd, kv_lens, bias)
    return o, (q, k, v, o, lse, bias, kv_lens, dropout_seed)


def _ring_bwd(axis_name, scale, causal, use_pallas, dropout_rate, bshd,
              res, do):
    q, k, v, o, lse, bias, kv_lens, dropout_seed = res
    dq, dk, dv, dtab = _ring_bwd_impl(
        q, k, v, o, lse, do, axis_name, scale, causal, use_pallas,
        dropout_rate, dropout_seed, bshd, kv_lens, bias)
    dbias = None
    if bias is not None:
        # this rank's partial — every (rank, step, piece) covers a
        # disjoint slice of the global score matrix, so the global table
        # grad is the plain cp-sum (each rank returns the full value: the
        # table is replicated, like the ring's traveling dkv convention)
        dtab = jax.lax.psum(dtab, axis_name)
        dbias = _bias_cotangent(bias, dtab)
    return (dq, dk, dv, dbias, _float0_like(kv_lens),
            _float0_like(dropout_seed))


_ring_core.defvjp(_ring_fwd, _ring_bwd)


def ring_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, axis_name: str = mesh_lib.CONTEXT_AXIS, causal: bool = False,
    scale: Optional[float] = None, impl: str = "auto",
    layout: str = "bhsd", kv_lens: Optional[jax.Array] = None,
    bias: Optional[BucketedBias] = None,
    dropout_rate: float = 0.0, dropout_seed: Optional[jax.Array] = None,
) -> jax.Array:
    """Attention over a sequence sharded along ``axis_name``: q/k/v are this
    device's (bh, s_local, d) shard — or, with ``layout='bshd'``, the
    seq-major (b, s_local, h, d) shard the projection GEMMs emit, which
    the kernels read with NO transpose round trip per ring step (the same
    layout economics as ``flash_attention(layout='bshd')``; requires the
    bshd tiling rule — head_dim 128 class). The full sequence is
    cp·s_local. Must run inside shard_map with the axis bound.

    Built on the flash kernel family: per ring step the arriving KV shard
    goes through :func:`_piece_fwd` (the Pallas kernel above its measured
    crossover) and the normalized (o, lse) pieces merge by the
    online-softmax fold — per-step memory is O(s_local·d); no (s_local ×
    s_local) score tensor ever exists outside kernel VMEM. Backward is the
    distributed flash backward (:func:`_ring_bwd_impl`): kv re-rotates the
    ring while a dkv accumulator travels with each shard, so residuals are
    O(s_local·d) too.

    ``causal=True`` REQUIRES the zigzag stripe layout: shard
    ``zigzag_shard(x, cp)`` over the axis (and ``zigzag_unshard`` the
    output). Device r then holds stripes (r, 2cp−1−r) of 2·cp total, and
    every ring step on every rank is exactly two *unmasked* stripe-pair
    flash calls — total FLOPs equal the lower-triangle minimum (half of
    full), perfectly load-balanced, with no masked-and-discarded work and
    no conditionals. (Contiguous causal sharding would leave rank 0 idle
    (cp−1)/cp of the time and burn 2× the FLOPs in masked work.)

    Grouped-query kv: the NARROW kv rotates the ring — group-times less
    ICI traffic — and the kernels read it via their index maps.

    The reference has no context parallelism at all (SURVEY §2.3); this is
    the long-context extension built to the repo's own kernel bar.

    ``dropout_rate > 0`` (``dropout_seed`` required; pass the SAME seed
    on every cp rank — ranks decorrelate internally): in-kernel probs
    dropout with a distinct mask stream per (rank, ring step, piece),
    re-derived identically in the hand-written backward. Each (q, k)
    pair is covered by exactly one piece, so masks stay i.i.d.
    Bernoulli over the global score matrix.

    ``bias``: a :class:`BucketedBias` (pass the SAME replicated table on
    every cp rank) — relative position bias under context parallelism.
    Because the bucketed form recomputes per tile from GLOBAL offsets,
    every ring piece derives its own (q_offset, k_offset) window and the
    bias follows the zigzag/contiguous sharding exactly; the bucket-table
    gradient is psum'd over the cp axis in the hand-written backward. A
    materialized (hb, sq, sk) array is REJECTED here: it cannot ride cp
    without replicating O(s²) HBM per device — exactly what this operand
    exists to avoid.

    ``kv_lens``: GLOBAL per-row valid kv lengths ((bh,) int32 flat /
    (b,) with ``layout='bshd'``, replicated over cp) — padded batches
    under context parallelism. Each piece masks its kv window by the
    clipped global length; pieces whose window is empty fold in with
    zero weight. Rows with length 0 return zeros.
    """
    d = q.shape[-1]
    scale = float(scale if scale is not None else 1.0 / d ** 0.5)
    if layout not in ("bhsd", "bshd"):
        raise ValueError(f"layout must be bhsd|bshd, got {layout!r}")
    bshd = layout == "bshd"
    if bias is not None:
        if not isinstance(bias, BucketedBias):
            raise ValueError(
                "ring_attention takes bias as a BucketedBias (the bucketed "
                "table recomputes per block under any sharding); a "
                "materialized (hb, sq, sk) array cannot ride context "
                "parallelism without O(s²) replication")
        _validate_bucketed(bias)
        heads = q.shape[2] if bshd else None
        if bshd and heads % bias.heads:
            raise ValueError(
                f"bias table heads ({bias.heads}) must divide q heads "
                f"({heads})")
        if not bshd and q.shape[0] % bias.heads:
            raise ValueError(
                f"bias table heads ({bias.heads}) must divide q rows "
                f"({q.shape[0]})")
    if kv_lens is not None:
        want = (q.shape[0],)
        if kv_lens.shape != want:
            raise ValueError(
                f"kv_lens must be {want} ({'per-batch' if bshd else 'per-row'}"
                f" global lengths); got {kv_lens.shape}")
        kv_lens = kv_lens.astype(jnp.int32)
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got "
                         f"{dropout_rate}")
    if dropout_rate > 0.0:
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires dropout_seed")
        dropout_seed = jnp.asarray(dropout_seed, jnp.int32)
    else:
        dropout_seed = None
    if q.shape[1] != k.shape[1] or k.shape[1] != v.shape[1]:
        # ring requires IDENTICAL q/kv sequence sharding — a longer kv
        # would silently stripe-slice at the wrong boundaries
        raise ValueError(
            f"ring attention requires equal q/k/v local sequence lengths; "
            f"got {q.shape[1]} / {k.shape[1]} / {v.shape[1]}")
    if bshd:
        if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
            raise ValueError(
                f"layout='bshd' takes (b, s, h, d) operands; got "
                f"{q.shape} / {k.shape}")
        if (q.shape[2] % k.shape[2] or q.shape[0] != k.shape[0]
                or k.shape[:2] != v.shape[:2]):
            raise ValueError(
                f"kv heads ({k.shape[2]}) must divide q heads "
                f"({q.shape[2]}) with matching batch/seq dims "
                f"({q.shape} vs {k.shape})")
    elif q.shape[0] % k.shape[0]:
        raise ValueError(
            f"kv rows ({k.shape[0]}) must divide q rows ({q.shape[0]}) "
            f"for grouped-query ring attention")
    s_loc = q.shape[1]
    if causal and s_loc % 2:
        raise ValueError(
            f"causal ring attention needs an even local sequence "
            f"({s_loc}) — two zigzag stripes per device")
    ss = s_loc // 2 if causal else s_loc
    if bshd:
        ok = bshd_kernel_ok(ss, ss, q.shape[2], d, q.dtype)
    else:
        # fp16 exclusion mirrors flash_attention's gate (Mosaic has no f16)
        ok = (ss % 128 == 0 and (d % 128 == 0 or d == 64)
              and q.dtype != jnp.float16)
    if (impl == "auto" and ss < flash_auto_crossover(d)
            and not _backend.interpret_forced()):
        impl = "xla"
    use_pallas = _backend.choose_impl(impl, ok) == "pallas"
    return _ring_core(q, k, v, bias, kv_lens, dropout_seed, axis_name,
                      scale, causal, use_pallas, dropout_rate, bshd)


# --- Ulysses attention (all-to-all sequence parallel) -------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _table_head_slice(table, axis_name, h_loc, heads):
    """This rank's (num_buckets, h_loc) column slice of the REPLICATED
    bucket table — with a hand VJP that scatters the local grad back to
    full width and psums it over the axis, so the replicated table's
    cotangent is the global sum (each head group contributes its own
    columns disjointly)."""
    start = jax.lax.axis_index(axis_name) * h_loc
    return jax.lax.dynamic_slice_in_dim(table, start, h_loc, axis=1)


def _ths_fwd(table, axis_name, h_loc, heads):
    return _table_head_slice(table, axis_name, h_loc, heads), ()


def _ths_bwd(axis_name, h_loc, heads, _res, d_local):
    start = jax.lax.axis_index(axis_name) * h_loc
    full = jnp.zeros((d_local.shape[0], heads), d_local.dtype)
    full = jax.lax.dynamic_update_slice_in_dim(full, d_local, start, axis=1)
    return (jax.lax.psum(full, axis_name),)


_table_head_slice.defvjp(_ths_fwd, _ths_bwd)


def ulysses_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, axis_name: str = mesh_lib.CONTEXT_AXIS, causal: bool = False,
    scale: Optional[float] = None, impl: str = "auto",
    kv_lens: Optional[jax.Array] = None,
    bias: Optional[BucketedBias] = None,
    dropout_rate: float = 0.0, dropout_seed: Optional[jax.Array] = None,
) -> jax.Array:
    """DeepSpeed-Ulysses-style sequence parallelism: q/k/v are this device's
    (batch, s_local, heads, head_dim) sequence shard with ALL heads; an
    ``all_to_all`` re-shards heads over ``axis_name`` while gathering the
    full sequence, unmodified :func:`flash_attention` runs per local head
    group, and a reverse ``all_to_all`` restores sequence sharding.

    Must run inside shard_map with the axis bound; requires
    ``heads % axis_size == 0``. Complements :func:`ring_attention`: Ulysses
    moves activations twice (cheap when heads >= devices, and each device
    sees the full sequence so any attention variant drops in); ring never
    materializes the full sequence on one device (memory-optimal, arbitrary
    cp). Backward is the transposed all-to-alls around flash's custom VJP —
    no hand-written grad needed.

    ``bias``: a :class:`BucketedBias` (same replicated table on every
    rank; table heads == q heads, or 1 for a broadcast bias). After the
    all-to-all each device holds the FULL sequence for a head subset, so
    the table simply slices to this rank's head columns
    (:func:`_table_head_slice` — its VJP scatters + psums the table grad)
    and rides unmodified :func:`flash_attention`; offsets stay 0 (global
    positions ARE local positions here). ``kv_lens``: (b,) per-batch
    GLOBAL valid kv lengths (replicated over the axis — the gathered
    sequence is the global one).
    """
    sp = jax.lax.axis_size(axis_name)
    b, s_local, h, d = q.shape
    if bias is not None:
        if not isinstance(bias, BucketedBias):
            raise ValueError(
                "ulysses_attention takes bias as a BucketedBias (a "
                "materialized array cannot ride context parallelism "
                "without O(s²) replication)")
        _validate_bucketed(bias)
        if bias.heads not in (1, h):
            raise ValueError(
                f"ulysses bias table heads ({bias.heads}) must be 1 "
                f"(broadcast) or equal q heads ({h}) — heads re-shard "
                f"over the axis, so per-head tables slice by rank")
    if kv_lens is not None:
        if kv_lens.shape != (b,):
            raise ValueError(
                f"ulysses kv_lens must be per-batch ({b},) global "
                f"lengths; got {kv_lens.shape}")
        kv_lens = kv_lens.astype(jnp.int32)
    if dropout_rate > 0.0:
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires dropout_seed")
        # each device attends a DIFFERENT head group over the full
        # sequence: fold the cp rank so head groups draw decorrelated
        # masks (pass the same base seed on every rank)
        dropout_seed = fold_dropout_seed(
            dropout_seed, jax.lax.axis_index(axis_name))
    h_kv = k.shape[2]
    if h % sp != 0 or h_kv % sp != 0:
        raise ValueError(
            f"ulysses_attention needs q heads ({h}) and kv heads ({h_kv}) "
            f"divisible by the {axis_name!r} axis size ({sp}); use "
            f"ring_attention otherwise")

    # (b, s/P, h, d) -> (b, s, h/P, d): scatter heads, gather sequence.
    # With grouped-query kv (h_kv < h) each tensor scatters its own head
    # count — the kv all_to_alls move group-times less data, and the
    # downstream flash kernel handles the grouping natively.
    def seq_to_head(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    qg, kg, vg = seq_to_head(q), seq_to_head(k), seq_to_head(v)
    s, h_loc = qg.shape[1], qg.shape[2]

    local_bias = bias
    if bias is not None and bias.heads == h:
        # per-head table: this rank attends heads [rank·h_loc, ...) — take
        # their columns (grad scatters + psums back through the hand VJP)
        local_bias = dataclasses.replace(
            bias, table=_table_head_slice(bias.table, axis_name, h_loc, h))

    if bshd_kernel_ok(s, s, h_loc, d, qg.dtype):
        # the all_to_all emits (b, s, h_loc, d) — exactly the kernels'
        # seq-major bshd layout, so attention runs on it directly; the
        # former unconditional bh-flat round trip (transpose+reshape on
        # every operand and the output, plus their autodiff transposes)
        # was pure layout traffic — the ~22% "head re-sharding" overhead
        # PERF.md measured was mostly these, not the collectives
        o = flash_attention(qg, kg, vg, causal=causal, scale=scale,
                            impl=impl, layout="bshd", kv_lens=kv_lens,
                            bias=local_bias,
                            dropout_rate=dropout_rate,
                            dropout_seed=dropout_seed)
    else:
        # bshd tiling ineligible (e.g. head_dim 64 with several local
        # heads) — keep the flat-kernel path rather than letting the bshd
        # XLA fallback materialize full (s, s) scores over the GATHERED
        # sequence at exactly the long-context scale Ulysses targets
        def to_bh(x):  # (b, s, x_heads, d) -> (b*x_heads, s, d)
            return x.transpose(0, 2, 1, 3).reshape(b * x.shape[2], s, d)

        o = flash_attention(to_bh(qg), to_bh(kg), to_bh(vg),
                            causal=causal, scale=scale, impl=impl,
                            kv_lens=(None if kv_lens is None
                                     else jnp.repeat(kv_lens, h_loc)),
                            bias=local_bias,
                            dropout_rate=dropout_rate,
                            dropout_seed=dropout_seed)
        o = o.reshape(b, h_loc, s, d).transpose(0, 2, 1, 3)
    # (b, s, h/P, d) -> (b, s/P, h, d): gather heads, re-scatter sequence
    return jax.lax.all_to_all(o, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)
