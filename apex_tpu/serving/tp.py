"""Tensor-parallel serving math: the shard-level helpers behind
``ServingEngine(plan=...)`` and ``DecodeEngine(plan=...)`` (ROADMAP
serving tier 2c — serve a model bigger than one chip).

The engines stay the owners of pools, schedulers, and the serve loop;
this module holds only what changes under ``tp >= 2``:

* **Eager validation** (:func:`validate_tp`): every illegal knob
  combination — kv heads that don't shard, a vocab the embedding can't
  split, a slot/chunk axis the rings can't chunk — raises a
  :class:`~apex_tpu.plan.parallel_plan.PlanError`-style named-knob
  message at ENGINE CONSTRUCTION, never as a shard_map shape error
  three dispatches in.
* **Vocab-parallel embedding** (:func:`vocab_embed`): masked local
  take + psum — bitwise identical to the full-table lookup (out-of-
  shard rows contribute exact zeros).
* **Ring-overlapped projections** (:func:`column_parallel` /
  :func:`row_parallel`): the PR-5 latency-hiding collective matmuls
  (``ops/collective_matmul.py``) applied to the decode/prefill GEMMs —
  each boundary collective rides the ring behind its GEMM
  (``overlap=True``), or degrades to the replicated-activation
  dot/psum form (``overlap=False``, the DecodeEngine path where batch
  axes aren't tp-divisible in general).
* **The psum-composed sampling tail** (:func:`row_argmax_tp` /
  :func:`sample_tp` / :func:`verify_greedy_tp`): each shard owns a
  contiguous vocab slice; the argmax composes exactly (global max via
  ``pmax``, first-max-lowest-index via ``pmin`` over offset local
  argmaxes — ``jnp.argmax``'s tie convention, so greedy under tp
  matches the tp=1 fused tail's decision function), and the Gumbel
  draw happens ONCE on the full vocab row (every shard draws the same
  ``(b, V)`` uniforms from the replicated key and slices its columns —
  the fused-sampling-tail fusion argument of arXiv:2502.17728 carried
  across the shard boundary).
* **The layer-math seam** (:class:`ShardedMath`): all a step body sees
  of the above — ``embed`` / ``qkv`` / ``attn_out`` / ``mlp`` /
  ``unembed`` and the tails at local head counts, the twin of
  ``serving.engine.ModelMath`` (tp = 1): each body is written once.
* **Cross-shard int8 scales** (:func:`quant_rows_tp`): local amax,
  ``pmax`` over tp, THEN the scale floor — scales come out bitwise
  identical to the tp=1 pool's (max composes through the floor), so
  the scale planes stay replicated and the paged kernel's int8 scale
  indirection is untouched.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.models.gpt import shard_params_for_tp
from apex_tpu.ops import collective_matmul as cm
from apex_tpu.parallel import mesh as mesh_lib
from apex_tpu.plan.parallel_plan import ParallelPlan, PlanError

TENSOR_AXIS = mesh_lib.TENSOR_AXIS


# --- eager validation ---------------------------------------------------------

def validate_tp(plan: ParallelPlan, config, *, engine: str,
                num_slots: Optional[int] = None,
                prefill_chunk: Optional[int] = None,
                num_blocks: Optional[int] = None,
                max_blocks_per_slot: Optional[int] = None,
                temperature: float = 0.0, top_k: int = 0,
                top_p: float = 1.0, has_rel_bias: bool = False,
                devices=None) -> int:
    """Validate a serving :class:`ParallelPlan` against the model and
    engine knobs; returns ``plan.tp``. Every failure names its knob in
    the :meth:`ParallelPlan.validate` message style — the tp serving
    contract is enforced HERE, eagerly, never as a deep shard_map
    shape error."""
    tp = plan.tp
    if tp < 2:
        return 1
    for name in ("dp", "pp", "cp", "ep"):
        v = getattr(plan, name)
        if v != 1:
            raise PlanError(
                f"{name}={v} with tp={tp}: {engine} shards the serving "
                f"programs over the tensor axis only; legal values are "
                f"{name}=1")
    ndev = len(jax.devices() if devices is None else devices)
    if ndev < tp:
        raise PlanError(
            f"tp={tp}: tensor-parallel serving needs one device per "
            f"shard and this process exposes {ndev}; legal values are "
            f"tp <= {ndev}")
    if config.kv_heads % tp:
        raise PlanError(
            f"tp={tp} with kv_heads={config.kv_heads}: each shard owns "
            f"a contiguous slice of kv heads (the paged pool shards on "
            f"the kv-head axis, keeping the decode kernel body "
            f"untouched), so kv_heads % tp == 0; legal values are "
            f"divisors of kv_heads")
    if config.num_heads % tp:
        raise PlanError(
            f"tp={tp} with num_heads={config.num_heads}: the qkv "
            f"projection column-shards by query head, so "
            f"num_heads % tp == 0; legal values are divisors of "
            f"num_heads")
    if config.vocab_size % tp:
        raise PlanError(
            f"tp={tp} with vocab_size={config.vocab_size}: the tied "
            f"embedding/unembedding shard the vocab row, so "
            f"vocab_size % tp == 0; legal values are divisors of "
            f"vocab_size (pad the vocab to a tp multiple)")
    if num_slots is not None and num_slots % tp:
        raise PlanError(
            f"num_slots={num_slots} with tp={tp}: the decode step's "
            f"overlapped projections chunk the slot axis around the "
            f"ring, so num_slots % tp == 0; legal values are multiples "
            f"of tp")
    if prefill_chunk is not None and prefill_chunk % tp:
        raise PlanError(
            f"prefill_chunk={prefill_chunk} with tp={tp}: the prefill "
            f"chunk's overlapped projections chunk the token axis "
            f"around the ring, so prefill_chunk % tp == 0; legal "
            f"values are multiples of tp")
    if num_blocks is not None and max_blocks_per_slot is not None \
            and num_blocks - 1 < max_blocks_per_slot:
        raise PlanError(
            f"num_blocks={num_blocks} with tp={tp}: the sharded pool "
            f"keeps ONE logical free list — num_blocks is a GLOBAL "
            f"count (each shard holds kv_heads/tp of every block), so "
            f"it is NOT multiplied by tp; {num_blocks - 1} usable "
            f"blocks cannot hold one full slot "
            f"(max_blocks_per_slot={max_blocks_per_slot}); legal "
            f"values are num_blocks >= {max_blocks_per_slot + 1}")
    if temperature > 0 and (top_k > 0 or top_p < 1.0):
        raise PlanError(
            f"top_k={top_k}/top_p={top_p} with tp={tp}: the tp "
            f"sampling tail composes the full-vocab-row Gumbel argmax "
            f"across shards and does not thread the top-k/top-p "
            f"filters; legal values are top_k=0 and top_p=1.0 (or "
            f"temperature=0 for greedy)")
    if has_rel_bias:
        raise PlanError(
            f"tp={tp} cannot serve a model with a decode relative-"
            f"position bias (the sharded decode path does not carry "
            f"the bucketed bias table); legal values are tp=1 for "
            f"this model")
    return tp


def tp_mesh(tp: int):
    """A dp=1 mesh over the first ``tp`` devices — the serving engines'
    mesh (``(1, 1, 1, tp)``; serving never widens dp)."""
    return mesh_lib.make_mesh(tensor_model_parallel_size=tp,
                              devices=jax.devices()[:tp])


def take_shard(params):
    """Drop the leading per-rank axis ``shard_params_for_tp`` added:
    inside ``shard_map`` under ``P('tp', ...)`` every leaf arrives as
    ``(1, ...)`` — this rank's slice at index 0."""
    return jax.tree.map(lambda a: a[0], params)


def prepare_params(params, tp: int, config, mesh):
    """tp == 1: passthrough. Under tp: the per-rank shards of the
    replicated tree (:func:`~apex_tpu.models.gpt.shard_params_for_tp`:
    a leading ``(tp,)`` axis a leaf), committed to ``mesh`` as ``P('tp')``."""
    if tp == 1:
        return params
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("tp"))
    return jax.tree.map(lambda a: jax.device_put(a, sh),
                        shard_params_for_tp(params, tp, config))


# --- vocab-parallel embedding -------------------------------------------------

def vocab_embed(weight_local, ids, *, axis=TENSOR_AXIS):
    """Vocab-parallel lookup: ``weight_local`` (V/tp, H) is this rank's
    contiguous vocab slice; out-of-shard ids contribute exact zeros and
    the psum reassembles the full-table lookup bitwise (0 + x == x)."""
    v_loc = weight_local.shape[0]
    r = jax.lax.axis_index(axis)
    local = ids - r * v_loc
    in_shard = (local >= 0) & (local < v_loc)
    x = jnp.take(weight_local, jnp.where(in_shard, local, 0), axis=0)
    x = jnp.where(in_shard[..., None], x, jnp.zeros((), x.dtype))
    return jax.lax.psum(x, axis)


# --- ring-overlapped projections ----------------------------------------------

def column_parallel(x, w_local, b_local=None, *, axis=TENSOR_AXIS,
                    seq_dim=0, overlap=True):
    """Column-parallel projection of REPLICATED activations ``x``
    (..., in) against this rank's output slice ``w_local`` (out/tp, in).
    ``overlap=True`` rides the bidirectional all-gather ring: each rank
    slices its own ``seq_dim`` chunk (the replicated operand IS every
    rank's shard) and :func:`~apex_tpu.ops.collective_matmul.
    all_gather_matmul` rebuilds the full extent behind the GEMM — no
    full-width all_gather in the program. Returns (..., out/tp)."""
    if overlap:
        tp = jax.lax.axis_size(axis)
        r = jax.lax.axis_index(axis)
        shard = x.shape[seq_dim] // tp
        xc = jax.lax.dynamic_slice_in_dim(x, r * shard, shard,
                                          axis=seq_dim)
        y = cm.all_gather_matmul(xc, w_local, axis_name=axis,
                                 seq_dim=seq_dim)
    else:
        y = jnp.dot(x, w_local.T)
    if b_local is not None:
        y = y + b_local
    return y


def row_parallel(y, w_local, b=None, *, axis=TENSOR_AXIS, seq_dim=0,
                 overlap=True):
    """Row-parallel projection of partial-feature activations ``y``
    (..., in/tp) against ``w_local`` (out, in/tp); the cross-shard sum
    rides the ring-psum of :func:`~apex_tpu.ops.collective_matmul.
    matmul_all_reduce` (``overlap=True``; bitwise-identical result on
    every rank) or a plain dot + psum. The REPLICATED bias ``b`` is
    added AFTER the reduction (adding it per-shard would count it tp
    times). Returns replicated (..., out)."""
    if overlap:
        out = cm.matmul_all_reduce(y, w_local, axis_name=axis,
                                   seq_dim=seq_dim)
    else:
        out = jax.lax.psum(jnp.dot(y, w_local.T), axis)
    if b is not None:
        out = out + b
    return out


# --- cross-shard int8 scales --------------------------------------------------

def quant_rows_tp(x, axes, axis_name=TENSOR_AXIS):
    """The tp form of the engines' ``_quant_rows``: the amax composes
    across shards BEFORE the floor/divide, so every shard quantizes its
    local kv heads against the GLOBAL row scale and the scale planes
    come out bitwise identical to the tp=1 pool's (``pmax`` commutes
    with the monotonic ``max(amax, tiny)/127``) — replicated, exactly
    the layout the paged kernel's scale indirection reads."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axes, keepdims=True)
    amax = jax.lax.pmax(amax, axis_name)
    scale = jnp.maximum(amax, 1e-20) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, jnp.squeeze(scale, axis=axes)


# --- the psum-composed sampling tail ------------------------------------------

def row_argmax_tp(s_local, *, axis=TENSOR_AXIS):
    """Full-vocab-row argmax from per-shard slices ``s_local``
    (..., V/tp), ties to the LOWEST global index — ``jnp.argmax``'s
    convention, composed exactly: the global max via ``pmax`` (float
    max is exact), then the smallest offset local-argmax among shards
    achieving it via ``pmin``. Two scalar-lane collectives; no O(V)
    gather."""
    v_loc = s_local.shape[-1]
    tp = jax.lax.axis_size(axis)
    r = jax.lax.axis_index(axis)
    lmax = jnp.max(s_local, axis=-1)
    gmax = jax.lax.pmax(lmax, axis)
    lidx = jnp.argmax(s_local, axis=-1).astype(jnp.int32)
    cand = jnp.where(lmax == gmax, lidx + r * v_loc,
                     jnp.int32(tp * v_loc))
    return jax.lax.pmin(cand, axis)


def gumbel_sample_tp(logits_local, key, *, temperature,
                     axis=TENSOR_AXIS):
    """Temperature sampling with the Gumbel draw made ONCE on the full
    vocab row: every shard draws the same ``(b, V)`` uniforms from the
    replicated key (identical bits — the draw count stays one per row,
    not one per shard), slices its own columns, and the perturbed
    argmax composes like :func:`row_argmax_tp`. The same
    uniform→Gumbel→argmax formulation as the fused tp=1 tail
    (``ops/pallas/sampling.py``), unfiltered (top-k/top-p are rejected
    eagerly under tp)."""
    v_loc = logits_local.shape[-1]
    tp = jax.lax.axis_size(axis)
    r = jax.lax.axis_index(axis)
    b = logits_local.shape[0]
    tiny = jnp.finfo(jnp.float32).tiny
    u = jax.random.uniform(key, (b, v_loc * tp), jnp.float32,
                           minval=tiny, maxval=1.0)
    u_loc = jax.lax.dynamic_slice_in_dim(u, r * v_loc, v_loc, axis=1)
    s = logits_local.astype(jnp.float32) * (1.0 / temperature)
    x = s + -jnp.log(-jnp.log(u_loc))
    return row_argmax_tp(x, axis=axis)


def sample_tp(logits_local, key, *, temperature, axis=TENSOR_AXIS):
    """The fused sampling tail's decision function over sharded logits:
    greedy argmax at ``temperature == 0``, single-full-row Gumbel
    otherwise. ``logits_local`` (b, V/tp) → (b,) int32."""
    if temperature == 0.0:
        return row_argmax_tp(logits_local, axis=axis)
    return gumbel_sample_tp(logits_local, key, temperature=temperature,
                            axis=axis)


def verify_greedy_tp(logits_local, drafted, *, axis=TENSOR_AXIS):
    """The spec round's greedy verify tail over sharded logits:
    ``logits_local`` (S, k+1, V/tp), ``drafted`` (S, k) int32 →
    ``(accept_len (S,), next_token (S,))``. The candidate rows compose
    via :func:`row_argmax_tp` (f32 cast first — ``verify_greedy``'s
    exact decision function) and the acceptance-prefix / corrected-
    token math is the kernel's own helpers, verbatim."""
    from apex_tpu.ops.pallas.verify import (NO_DRAFT, accepted_prefix_len,
                                            select_row)
    s = logits_local.shape[0]
    cand = row_argmax_tp(logits_local.astype(jnp.float32), axis=axis)
    drafted_pad = jnp.concatenate(
        [drafted.astype(jnp.int32),
         jnp.full((s, 1), NO_DRAFT, jnp.int32)], axis=1)
    # the helpers take per-row operands as (S, k+1, 1) columns and
    # return (S, 1, 1) cells
    cand = cand[..., None]
    a = accepted_prefix_len(cand == drafted_pad[..., None])
    return a[:, 0, 0], select_row(cand, a)[:, 0, 0]


# --- the layer-math seam ------------------------------------------------------

class ShardedMath:
    """How one layer's linear algebra is done on a tp mesh — the
    ``plan.tp >= 2`` twin of ``serving.engine.ModelMath``, for a step
    body running INSIDE ``shard_map``: same operations, same shapes up
    to LOCAL head counts ((…, s, heads/tp, d)) and a vocabulary SHARD
    of logits, so the bodies never name an axis. ``overlap`` picks the
    ring-decomposed collective matmuls (the serving engine: slots and
    chunks are tp-divisible) or plain dot + psum (``DecodeEngine``:
    batch and prompt lengths are not). Tree verification and fp8 rows
    have no sharded form yet: a psum-composed ``verify_tree`` here and
    ``(qmax, qdtype)`` on :func:`quant_rows_tp` are all they need."""

    def __init__(self, config, *, overlap: bool, temperature: float = 0.0,
                 axis=TENSOR_AXIS):
        self.config, self.overlap = config, overlap
        self.temperature, self.axis = temperature, axis

    def shard(self, params):
        return take_shard(params)

    def _ring(self, fn, p, x):
        # the ring chunks the first axis tp divides: the slot array
        # (S, …) or the prefill chunk (1, C, …) — validate_tp saw to it
        seq_dim = 0 if x.shape[0] % jax.lax.axis_size(self.axis) == 0 else 1
        return fn(x, p["weight"], p.get("bias"), axis=self.axis,
                  seq_dim=seq_dim, overlap=self.overlap)

    def embed(self, params, tokens):
        return vocab_embed(params["embedding"]["weight"], tokens,
                           axis=self.axis)

    def qkv(self, layer, h_in):
        """The packed projection's local columns, sliced once:
        q (…, s, h/tp, d), k/v (…, s, h_kv/tp, d)."""
        c, tp = self.config, jax.lax.axis_size(self.axis)
        y = self._ring(column_parallel, layer["qkv"], h_in)
        h, h_kv, d = c.num_heads // tp, c.kv_heads // tp, c.head_dim
        return tuple(a.reshape(*a.shape[:-1], -1, d) for a in jnp.split(
            y, [h * d, (h + h_kv) * d], axis=-1))

    def attn_out(self, layer, ctx):
        return self._ring(row_parallel, layer["attn_out"],
                          ctx.reshape(*ctx.shape[:-2], -1))

    def mlp(self, layer, h):
        h = self._ring(column_parallel, layer["mlp_up"], h)
        return self._ring(row_parallel, layer["mlp_down"],
                          jax.nn.gelu(h, approximate=True))

    def unembed(self, params, x):
        return jnp.dot(x, params["embedding"]["weight"].T)

    def sample(self, logits, key):
        return sample_tp(logits, key, temperature=self.temperature,
                         axis=self.axis)

    def verify(self, logits, drafted, key):
        return verify_greedy_tp(logits, drafted, axis=self.axis)

    def quant_rows(self, x, axes):
        return quant_rows_tp(x, axes, self.axis)
