"""ServingEngine: continuous-batching generation over a paged KV cache.

The device side of :mod:`apex_tpu.serving` — compiled programs with one
set of avals each for the lifetime of the engine (their contracts are
the ``_*_body`` docstrings below):

* ``prefill_chunk(params, pool, table_row, tokens, start, live, key)``
  — one fixed-size chunk of ONE slot's prompt: a block scatter at
  traced ids, chunk queries × the slot's gathered padded cache, and the
  LAST chunk's final row samples the request's first token.
* ``decode_step(params, pool, tables, tokens, lengths, key)`` — one
  token for EVERY slot: per-slot ``(block, row)`` writes, the paged
  :func:`apex_tpu.ops.decode_attention`, the fused sampling tail.
* ``spec_step(..., drafted, key)`` / ``spec_tree_step(..., parents,
  anc, levels, key)`` — the speculative rounds (``serve(draft=...)``):
  k+1 chain rows or a whole draft tree scored per slot in one dispatch,
  the fused verify tails emit ``(accept_len, next_token)``.

Each body is written ONCE, as positions + block ids + a loop over
layers + a tail, against three seams: the layer math (:class:`ModelMath`
here, :class:`apex_tpu.serving.tp.ShardedMath` under ``plan.tp >= 2`` —
the same bodies run inside ``shard_map``), :func:`cached_attention`
(the one dense attention over a gathered prefix) and the pool's
``_write_blocks`` / ``_write_rows`` / ``_gather_slot``.

All donate the pool: XLA updates the cache in place, so a step's HBM
traffic is the live cache read plus one token's writes — never a pool
copy. Under a quantized ``kv_dtype`` (``"int8"`` or ``"fp8_e4m3"``)
the pool stores 1-byte k/v cells with per-block-row fp32 scales
alongside (quantize on write; dequantize in-VMEM inside the paged
decode kernel), halving the bytes the HBM-bound decode stream pays —
the float pool stays the parity oracle. Everything dynamic about
traffic stays in :class:`~apex_tpu.serving.scheduler.Scheduler` on the
host; churn reaches the device only as operand *contents*, which is why
``decode_step._cache_size()`` stays 1 across arbitrary admit/evict
(asserted by ``tests/test_serving.py``).
"""

from __future__ import annotations

import contextlib
import functools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models.gpt import GPTModel
from apex_tpu.monitor import registry as monitor_registry
from apex_tpu.monitor import spans as monitor_spans
from apex_tpu.monitor import trace as monitor_trace
from apex_tpu.ops import (fused_layer_norm, fused_sample, fused_verify,
                          fused_verify_tree)
from apex_tpu.ops.decode_attention import decode_attention
from apex_tpu.ops.pallas.attention import NEG_INF
from apex_tpu.parallel import mesh as mesh_lib
from apex_tpu.serving import tp as tp_serving
from apex_tpu.serving.kv_blocks import (DEAD_BLOCK, BlockAllocator,
                                        PrefixCache)
from apex_tpu.serving.scheduler import Request, Scheduler, SLOPolicy
from apex_tpu.serving.telemetry import ServeTelemetry


#: legal kv_dtype values and their (qmax, storage dtype): int8 rounds
#: into [-127, 127]; fp8_e4m3 keeps a mantissa and scales amax onto the
#: format's finite ceiling (448) — same per-block-row fp32 scale planes,
#: same 1 byte/cell, so the two pools share every write/gather site
KV_QUANT_SPECS = {
    "int8": (127.0, jnp.int8),
    "fp8_e4m3": (448.0, jnp.float8_e4m3fn),
}


def _quant_rows(x, axes, *, qmax=127.0, qdtype=jnp.int8):
    """Symmetric per-row quantization: one fp32 scale per row (``axes``
    reduced away — kv heads and head_dim share it, because the write
    sites land one token row at a time). Integer targets round into
    [-qmax, qmax] (int8's [-127, 127]); float targets (fp8) keep their
    own mantissa and just clip at the format's amax. The tiny floor
    keeps an all-zero row's scale finite (dead-block writes, padding) —
    it dequantizes back to exact zeros."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-20) / qmax
    y = xf / scale
    if jnp.issubdtype(jnp.dtype(qdtype), jnp.integer):
        q = jnp.clip(jnp.round(y), -qmax, qmax).astype(qdtype)
    else:
        q = jnp.clip(y, -qmax, qmax).astype(qdtype)
    return q, jnp.squeeze(scale, axis=axes)


class ModelMath:
    """How one layer's linear algebra is done at tp = 1 — the seam every
    step body of both engines is written against: ``embed`` / ``qkv`` /
    ``attn_out`` / ``mlp`` / ``unembed`` and the tails ``sample`` /
    ``verify`` / ``verify_tree`` / ``quant_rows``, here the model's own
    methods at full head counts. :class:`apex_tpu.serving.tp.
    ShardedMath` is the same surface on a tp mesh; an engine picks one
    at construction and no body knows which. ``sample`` is the engine's
    sampling program ``(logits, key, **sampling)``; ``sampling``
    (temperature / top_k / top_p) also feeds the fused verify tails."""

    def __init__(self, model: GPTModel, sample, *, qmax=127.0,
                 qdtype=jnp.int8, **sampling):
        self.model, self._sample, self._sampling = model, sample, sampling
        self._quant = dict(qmax=qmax, qdtype=qdtype)

    def shard(self, params):
        return params

    def embed(self, params, tokens):
        return self.model.embedding(params["embedding"], tokens)

    def qkv(self, layer, h_in):
        """(…, s, H) → seq-major q (…, s, h, d), k/v (…, s, h_kv, d)."""
        return self.model._proj_qkv_bshd(layer, h_in)

    def attn_out(self, layer, ctx):
        return self.model._proj_attn_out(layer, ctx)

    def mlp(self, layer, h):
        return self.model._mlp(layer, h)

    def unembed(self, params, x):
        return self.model.unembed(params, x)

    def sample(self, logits, key):
        return self._sample(logits, key, **self._sampling)

    def verify(self, logits, drafted, key):
        return fused_verify(logits, drafted, key, **self._sampling)

    def verify_tree(self, logits, tokens, parents, anc, key):
        return fused_verify_tree(logits, tokens, parents, anc, key,
                                 **self._sampling)

    def quant_rows(self, x, axes):
        return _quant_rows(x, axes, **self._quant)


def cached_attention(q, k_all, v_all, mask, tree=None):
    """THE dense attention of every multi-token serving step (prefill
    chunk, chain round, tree round — both engines): queries ``q``
    (b, C, h, d), grouped by kv head, against each slot's whole cached
    prefix ``k_all``/``v_all`` (b, h_kv, max_s, d) — or ONE slot's,
    without the leading axis, for ``b == 1`` — under ``mask``
    (broadcastable to the scores (…, h_kv, group, C, max_s)). ``tree``
    adds a second key/value set ``(k_t, v_t, mask_t)`` — the tree
    round's own nodes (b, N, h_kv, d) — sharing the ONE softmax, exactly
    the distribution the committed-path decode would compute. Returns
    the context (b, C, h, d).

    It scores all ``max_s`` rows whatever the live length (PERF.md §6,
    PR 23: 282 of a 312 ms chunk) — ROADMAP S4 lands here, once."""
    one_slot = k_all.ndim < q.ndim
    *lead, C, h, d = q.shape[one_slot:]
    h_kv = k_all.shape[-3]
    qg = jnp.moveaxis((q[0] if one_slot else q).reshape(
        *lead, C, h_kv, h // h_kv, d), -4, -2)

    def scores(k, mask):
        s = jnp.einsum("...hgcd,...hsd->...hgcs", qg, k.astype(qg.dtype),
                       preferred_element_type=jnp.float32) * (1.0 / d ** 0.5)
        return jnp.where(mask, s, NEG_INF)

    def mix(p, v):
        return jnp.einsum("...hgcs,...hsd->...hgcd", p.astype(v.dtype), v)
    s = scores(k_all, mask)
    if tree is not None:
        k_t, v_t, mask_t = tree
        kt, vt = (jnp.swapaxes(a[0] if one_slot else a, -3, -2)
                  for a in (k_t, v_t))
        s = jnp.concatenate([s, scores(kt, mask_t)], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    n = k_all.shape[-2]
    ctx = (mix(p, v_all) if tree is None
           else mix(p[..., :n], v_all) + mix(p[..., n:], vt))
    return jnp.moveaxis(ctx, -2, -4).reshape(q.shape)


def winning_path_levels(anc, depth_vec, j_star, levels):
    """The tree round's commit selector (b, depth+1, N1): one-hot over
    the nodes of ``j_star``'s root path, one row per level (root =
    level 0 = the pending token), from the ancestor-or-self matrix
    ``anc`` (b, N1, N1) and its row sums ``depth_vec``."""
    ii = jnp.arange(anc.shape[-1], dtype=jnp.int32)
    onpath = jnp.einsum(
        "si,sin->sn", (ii[None] == j_star[:, None]).astype(jnp.float32),
        anc.astype(jnp.float32))  # (b, N1)
    return onpath[:, None, :] * (
        depth_vec[:, None, :] == levels[None, :, None]).astype(jnp.float32)


def run_layers(m, params, x, attend):
    """The stack, once for every step body of both engines: per layer
    pre-LN → the seam's projections → ``attend(i, q, k, v)`` — the
    step's own cache write and attention, returning the context
    (…, s, heads, d) — → output projection → pre-LN MLP, a residual
    around each half; then the final LN."""
    for i in range(jax.tree.leaves(params["layers"])[0].shape[0]):
        layer = jax.tree.map(lambda a: a[i], params["layers"])
        q, k, v = m.qkv(layer, fused_layer_norm(
            x, layer["ln1_w"], layer["ln1_b"]))
        x = x + m.attn_out(layer, attend(i, q, k, v))
        x = x + m.mlp(layer, fused_layer_norm(
            x, layer["ln2_w"], layer["ln2_b"]))
    return fused_layer_norm(x, params["lnf_w"], params["lnf_b"])


def _pos_rows(params, pos):
    """Learned position rows at traced ``pos`` (clamped to the table)."""
    ptab = params["pos_embedding"]
    return jnp.take(ptab, jnp.minimum(pos, ptab.shape[0] - 1), axis=0)


@dataclass
class ServeStats:
    """Host-side accounting of one :meth:`ServingEngine.serve` call."""

    decode_steps: int = 0
    prefill_chunks: int = 0
    blocks_high_water: int = 0
    swaps: int = 0
    # speculative rounds (serve(draft=...)): a spec round is one
    # decode-width dispatch that can emit up to k+1 tokens per slot
    spec_rounds: int = 0
    spec_drafted: int = 0
    spec_accepted: int = 0
    # tree rounds (serve(draft=<tree drafter>)): spec_drafted counts
    # DEPTH rows (the chain-equivalent denominator — acceptance rates
    # stay comparable across tree and chain), spec_nodes the verify
    # rows actually scored (branching x depth per slot per round), and
    # spec_degraded the rounds the tree→chain→plain headroom ladder
    # stepped down instead of stalling
    tree_rounds: int = 0
    spec_nodes: int = 0
    spec_degraded: int = 0
    # per-SLOT spec rounds (spec_rounds counts dispatches; each live
    # slot in a dispatch is one slot-round — the efficiency denominator)
    spec_slot_rounds: int = 0
    occupancy_samples: List[int] = field(default_factory=list)

    def occupancy_pct(self, num_slots: int) -> Optional[float]:
        if not self.occupancy_samples:
            return None
        return (100.0 * sum(self.occupancy_samples)
                / (len(self.occupancy_samples) * num_slots))

    @property
    def spec_acceptance_rate(self) -> float:
        """Accepted drafts / drafted tokens (0.0 before any round)."""
        return (self.spec_accepted / self.spec_drafted
                if self.spec_drafted else 0.0)

    @property
    def spec_efficiency(self) -> float:
        """Emitted tokens per verify-row scored — the tree/chain
        cost-normalized yield (each per-slot round scores ``nodes + 1``
        rows and emits ``accepted + 1`` tokens); 0.0 before any round.
        The adaptive-vs-fixed bench comparison ranks on THIS: a wider
        tree that lifts acceptance but wastes more rows must win here,
        not just on raw acceptance."""
        rows = self.spec_nodes + self.spec_slot_rounds
        return ((self.spec_accepted + self.spec_slot_rounds) / rows
                if rows else 0.0)


class ServingEngine:
    """Continuous-batching serving over a :class:`GPTModel`.

    ``engine = ServingEngine(model, num_slots=8, block_size=128)``;
    ``results = engine.serve(params, requests)`` — each
    :class:`~apex_tpu.serving.scheduler.Request` comes back with its
    generated tokens and latency stamps.

    Knobs (all static — they shape the two compiled programs):

    * ``num_slots`` — concurrent streams; the decode step's batch width.
    * ``block_size`` — cache page granularity; 128 on TPU (the paged
      kernel's lane-tiling constraint), smaller off-TPU if desired.
    * ``max_seq_len`` — per-slot logical cap (prompt + generated - 1
      rows); must be a ``block_size`` multiple. Defaults to the model's
      position table rounded DOWN to the block grid.
    * ``num_blocks`` — pool capacity + 1 dead block. Defaults to full
      capacity (``num_slots * max_seq_len/block_size + 1``); size it
      DOWN to what live traffic needs — that is the point of paging —
      and the scheduler turns the shortfall into prefix-cache
      reclamation, then preemption (evict-and-recompute), instead of
      failure or an admission stall.
    * ``prefill_chunk`` — prompt tokens per prefill step (a
      ``block_size`` multiple); smaller chunks interleave tighter with
      decode (less per-step jitter), larger chunks reach the first
      token sooner.
    * ``temperature`` / ``top_k`` / ``top_p`` — the fused sampling
      tail's static program (greedy when ``temperature == 0``).
    * ``plan`` — a :class:`~apex_tpu.plan.parallel_plan.ParallelPlan`
      with ``tp >= 2`` serves the model tensor-parallel: the paged
      pool shards contiguous kv-head slices per chip (ONE logical free
      list — allocator/tables stay host-side and identical across
      shards), the projections ride the ring-overlapped collective
      matmuls, and the fused sampling tail psum-composes so greedy
      output stays token-identical to tp=1 (see
      :mod:`apex_tpu.serving.tp`). Validated eagerly HERE.
    """

    def __init__(self, model: GPTModel, *, num_slots: int,
                 block_size: int = 128, num_blocks: Optional[int] = None,
                 max_seq_len: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 cache_dtype: Any = None, kv_dtype: Optional[str] = None,
                 temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 1.0, plan=None):
        model.check_decode_supported()
        self.model = model
        c = self.config = model.config
        # quantized KV pools (ROADMAP item 3b + fp8 sibling): 1 byte per
        # cell instead of the cache dtype's 2, halving the bytes the
        # decode kernel streams and doubling live-token capacity; the
        # float pool (kv_dtype=None, dtype = cache_dtype) stays as the
        # parity oracle. int8 and fp8_e4m3 share the per-block-row fp32
        # scale layout and every write/gather site; only (qmax, storage
        # dtype) differ (see KV_QUANT_SPECS). Validated HERE — an
        # unsupported value or model composition must name the knob,
        # never surface as a deep XLA dtype/shape error mid-serve.
        if kv_dtype not in (None, *KV_QUANT_SPECS):
            legal = ", ".join(repr(k) for k in KV_QUANT_SPECS)
            raise ValueError(
                f"kv_dtype must be None (float pool in cache_dtype) or "
                f"one of {legal} (per-block-row scales, dequantized "
                f"in-kernel); got {kv_dtype!r}")
        if kv_dtype is not None \
                and getattr(model, "decode_rel_bias", None) is not None:
            raise ValueError(
                f"kv_dtype={kv_dtype!r} cannot serve a model with a "
                "decode relative-position bias (the quantized paged "
                "kernel path does not carry the bucketed bias) — serve "
                "this model with the float pool (kv_dtype=None)")
        if kv_dtype == "fp8_e4m3" and plan is not None \
                and int(getattr(plan, "tp", 1)) > 1:
            raise ValueError(
                "kv_dtype='fp8_e4m3' is tp=1 only for now (the "
                "tensor-parallel quantize path is int8-specific) — "
                "serve fp8 pools single-chip or use kv_dtype='int8'")
        self.kv_dtype = kv_dtype
        self.quantized = kv_dtype is not None
        self._qmax, self._qdtype = KV_QUANT_SPECS.get(
            kv_dtype, (127.0, jnp.int8))
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.block_size = int(block_size)
        max_s = int(max_seq_len if max_seq_len is not None
                    else c.max_seq_len - c.max_seq_len % self.block_size)
        if max_s < self.block_size or max_s % self.block_size:
            raise ValueError(
                f"max_seq_len ({max_s}) must be a positive multiple of "
                f"block_size ({self.block_size}) — round up: "
                f"max_seq_len={-(-max_s // self.block_size) * self.block_size}")
        if max_s > c.max_seq_len:
            raise ValueError(
                f"max_seq_len ({max_s}) exceeds the model's position "
                f"table ({c.max_seq_len})")
        self.max_s = max_s
        self.max_blocks_per_slot = max_s // self.block_size
        self.num_slots = int(num_slots)
        if self.num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        full = self.num_slots * self.max_blocks_per_slot + 1
        self.num_blocks = int(num_blocks if num_blocks is not None else full)
        self.prefill_chunk_size = int(
            prefill_chunk if prefill_chunk is not None else self.block_size)
        if (self.prefill_chunk_size < self.block_size
                or self.prefill_chunk_size % self.block_size):
            raise ValueError(
                f"prefill_chunk ({self.prefill_chunk_size}) must be a "
                f"positive multiple of block_size ({self.block_size})")
        self.cache_dtype = cache_dtype or c.dtype
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        # tensor-parallel serving (ROADMAP tier 2c): plan.tp >= 2 shards
        # the pool/projections/sampling tail across chips; tp == 1 (or
        # plan=None) leaves every path byte-identical to the seed
        self.plan = plan
        self.tp = int(plan.tp) if plan is not None else 1
        self._mesh = None
        self._swap_ref = None
        # the layer-math seam: WHICH linear algebra the step bodies run
        # (local dots or the rings) is decided here and nowhere else
        self._math = ModelMath(
            model, fused_sample, qmax=self._qmax, qdtype=self._qdtype,
            temperature=self.temperature, top_k=self.top_k,
            top_p=self.top_p)
        if self.tp > 1:
            tp_serving.validate_tp(
                plan, c, engine="ServingEngine",
                num_slots=self.num_slots,
                prefill_chunk=self.prefill_chunk_size,
                num_blocks=self.num_blocks,
                max_blocks_per_slot=self.max_blocks_per_slot,
                temperature=self.temperature, top_k=self.top_k,
                top_p=self.top_p,
                has_rel_bias=getattr(model, "decode_rel_bias",
                                     None) is not None)
            self._mesh = tp_serving.tp_mesh(self.tp)
            # these programs ride the ring: slots, chunks are tp-divisible
            self._math = tp_serving.ShardedMath(
                c, overlap=True, temperature=self.temperature)
            P = jax.sharding.PartitionSpec
            kv, rep = P(None, None, "tp"), P()
            pool_spec = ({"k": kv, "v": kv, "k_scale": rep,
                          "v_scale": rep} if self.quantized
                         else {"k": kv, "v": kv})
            self._pool_spec = pool_spec
            # the SAME bodies, shard_mapped in place: params arrive
            # P('tp') on the leading per-rank axis, pool k/v shard the
            # kv-head axis (block ids/tables/free list stay GLOBAL — one
            # logical pool), everything else replicates; sampled tokens
            # come back replicated (the psum-composed tail) and logits
            # reassemble the vocab row as output sharding — never an
            # all_gather inside the program (the jaxpr gate's witness).
            # The tree round stays unmapped: serve() refuses it under tp
            smap = functools.partial(mesh_lib.shard_map, mesh=self._mesh)
            self._prefill_chunk_body = smap(
                self._prefill_chunk_body,
                in_specs=(P("tp"), pool_spec, rep, rep, rep, rep, rep),
                out_specs=(pool_spec, rep, P("tp")))
            self._decode_step_body = smap(
                self._decode_step_body,
                in_specs=(P("tp"), pool_spec, rep, rep, rep, rep),
                out_specs=(pool_spec, rep, P(None, "tp")))
            self._spec_step_body = smap(
                self._spec_step_body,
                in_specs=(P("tp"), pool_spec, rep, rep, rep, rep, rep),
                out_specs=(pool_spec, rep, rep))
        self.last_stats: Optional[ServeStats] = None
        # the last serve run's final pool (set by _serve_loop): the
        # disaggregated prefill role exports its warm blocks from here
        self.last_pool = None
        # pending weight hot-swap: (at_step, new_params, label) —
        # applied by the serve loop BETWEEN dispatch steps (see
        # request_swap)
        self._pending_swap = None
        # one jitted executable each; both donate the pool (argnums:
        # params=0, pool=1, ... — the cache updates in place)
        self.prefill_chunk = jax.jit(self._prefill_chunk,
                                     donate_argnums=(1,))
        self.decode_step = jax.jit(self._decode_step, donate_argnums=(1,))
        # the speculative round (serve(draft=...)): every decoding slot
        # verifies k drafted tokens in ONE dispatch; avals depend only
        # on the static draft length, so across rounds and churn it
        # compiles exactly once like the other two
        self.spec_step = jax.jit(self._spec_step, donate_argnums=(1,))
        # the TREE speculative round (serve(draft=<tree drafter>)):
        # avals depend only on the (num_nodes+1, depth+1) topology, so
        # there is one pinned executable per (depth, branching) in use
        # — the adaptive controller's whole choice set compiles once
        self.spec_tree_step = jax.jit(self._tree_step, donate_argnums=(1,))

    # --- pool ----------------------------------------------------------------

    def init_pool(self) -> Dict[str, jax.Array]:
        """The zeroed block pool:
        ``{"k"/"v": (layers, num_blocks, kv_heads, block_size, head_dim)}``
        — block 0 is the dead block (see kv_blocks). Under a quantized
        ``kv_dtype`` (``"int8"`` / ``"fp8_e4m3"``) the k/v arrays hold
        1-byte cells and per-block-row fp32 scales ride alongside as
        ``k_scale``/``v_scale`` ``(layers, num_blocks, block_size)`` —
        one pool tree either way, its avals fixed for the engine's
        lifetime."""
        c = self.config
        shape = (c.num_layers, self.num_blocks, c.local_kv_heads,
                 self.block_size, c.head_dim)
        if self.quantized:
            sshape = (c.num_layers, self.num_blocks, self.block_size)
            pool = {"k": jnp.zeros(shape, self._qdtype),
                    "v": jnp.zeros(shape, self._qdtype),
                    "k_scale": jnp.zeros(sshape, jnp.float32),
                    "v_scale": jnp.zeros(sshape, jnp.float32)}
        else:
            pool = {"k": jnp.zeros(shape, self.cache_dtype),
                    "v": jnp.zeros(shape, self.cache_dtype)}
        if self.tp > 1:
            # commit the pool to its mesh sharding up front (k/v split
            # on kv heads, scale planes replicated): the first dispatch
            # then sees the same committed shardings as every later one
            # — an uncommitted->committed transition would be a second
            # jit cache entry, breaking the _cache_size() == 1 contract
            pool = {
                name: jax.device_put(a, jax.sharding.NamedSharding(
                    self._mesh, self._pool_spec[name]))
                for name, a in pool.items()}
        return pool

    def _prepare_params(self, params):
        """tp == 1: passthrough; under tp the per-rank shards, committed
        to the mesh (:func:`apex_tpu.serving.tp.prepare_params`)."""
        return tp_serving.prepare_params(params, self.tp, self.config,
                                         self._mesh)

    def pool_bytes(self) -> int:
        """HBM footprint of the whole pool (both k and v, plus the
        scale planes under int8)."""
        c = self.config
        cells = (c.num_layers * self.num_blocks * c.local_kv_heads
                 * self.block_size * c.head_dim)
        if self.quantized:
            scales = c.num_layers * self.num_blocks * self.block_size
            return 2 * cells + 2 * scales * 4
        return 2 * cells * jnp.dtype(self.cache_dtype).itemsize

    # --- weight hot-swap -----------------------------------------------------

    @staticmethod
    def _validate_swap_avals(old, new) -> None:
        """The hot-swap contract: the new tree must be a contents-only
        mutation — same structure, same shape/dtype per leaf — so both
        jitted programs keep their compiled executables (stable avals;
        the jit caches stay pinned at 1 through a swap). Every mismatch
        names its leaf path eagerly; a silent aval drift would instead
        surface as a RECOMPILE mid-serve, exactly the failure mode the
        zero-recompile contract exists to prevent."""
        old_paths = jax.tree_util.tree_flatten_with_path(old)
        new_paths = jax.tree_util.tree_flatten_with_path(new)
        if jax.tree.structure(old) != jax.tree.structure(new):
            ok = {jax.tree_util.keystr(p) for p, _ in old_paths[0]}
            nk = {jax.tree_util.keystr(p) for p, _ in new_paths[0]}
            extra, missing = sorted(nk - ok), sorted(ok - nk)
            raise ValueError(
                f"hot-swap params tree mismatch: new tree "
                f"{'adds ' + str(extra) if extra else ''}"
                f"{' and ' if extra and missing else ''}"
                f"{'drops ' + str(missing) if missing else ''}"
                f"{'' if extra or missing else 'has a different structure'}"
                f" — a swap is contents-only (same model, new weights)")
        for (path, a), (_, b) in zip(old_paths[0], new_paths[0]):
            if jnp.shape(a) != jnp.shape(b) or \
                    jnp.asarray(a).dtype != jnp.asarray(b).dtype:
                raise ValueError(
                    f"hot-swap aval mismatch at {jax.tree_util.keystr(path)}: "
                    f"serving {jnp.shape(a)}/{jnp.asarray(a).dtype}, new "
                    f"checkpoint {jnp.shape(b)}/{jnp.asarray(b).dtype} — "
                    f"a swap must keep every aval (restore_params(..., "
                    f"like=current_params) produces a matching tree)")

    def request_swap(self, new_params, *, at_step: Optional[int] = None,
                     source: Optional[str] = None) -> None:
        """Queue a weight hot-swap for the live serve loop: the NEXT
        loop iteration whose dispatch counter has reached ``at_step``
        (immediately when ``None``) replaces the params reference
        BETWEEN dispatch steps — in-flight requests keep their KV cache
        and finish against the new weights without dropping. Avals are
        validated against the live params at apply time (an eager,
        leaf-naming error — never a mid-serve recompile); ``source``
        labels the ``swap`` lifecycle event (e.g. the checkpoint step).

        One swap is pending at a time (a newer request replaces an
        unapplied one), and an unapplied swap does NOT outlive the
        serve call — if ``at_step`` is never reached the swap is
        dropped when ``serve`` returns (``last_stats.swaps == 0`` is
        the tell), never silently applied to a later run.

        Typical use with the sharded checkpoint subsystem::

            new = apex_tpu.ckpt.restore_params(ckpt_dir, like=params)
            engine.request_swap(new, source="step_00000042")
        """
        self._pending_swap = (at_step, new_params, source)

    def _maybe_swap(self, params, nstep: int, tel, stats, now: float):
        if self._pending_swap is None:
            return params
        at_step, new_params, source = self._pending_swap
        if at_step is not None and nstep < at_step:
            return params
        self._pending_swap = None
        t0 = time.perf_counter()
        # under tp the live params are the SHARDED tree; the contract is
        # stated (and validated) against the replicated tree the caller
        # handed serve() — the swap error names the caller's leaves
        self._validate_swap_avals(
            self._swap_ref if self.tp > 1 else params, new_params)
        stats.swaps += 1
        if self.tp > 1:
            self._swap_ref = new_params
            new_params = self._prepare_params(new_params)
        if tel is not None:
            # the measured validate+rebind pause: attribution carves it
            # out of the decode time of every mid-decode request
            tel.on_swap(nstep, now, source=source,
                        dur_ms=(time.perf_counter() - t0) * 1e3)
        return new_params

    # --- the pool's one write and one gather ----------------------------------

    def _write(self, pool, at, at_scale, k, v, axes):
        """k/v land at ``pool["k"/"v"][at]``; a quantized pool quantizes
        on write — one scale per token row over ``axes`` (kv heads and
        head_dim), at the SAME block coordinates, so the dead-block
        redirect covers the scale planes too."""
        out = {}
        for n, x in (("k", k), ("v", v)):
            if self.quantized:
                x, scale = self._math.quant_rows(x, axes)
                out[n + "_scale"] = pool[n + "_scale"].at[at_scale].set(scale)
            out[n] = pool[n].at[at].set(x.astype(pool[n].dtype))
        return out

    def _write_blocks(self, pool, i, ids, kb, vb):
        """Prefill's whole-block scatter: ``kb``/``vb``
        (C/B, h_kv, B, d) into layer ``i`` at traced block ``ids``."""
        return self._write(pool, (i, ids), (i, ids), kb, vb, (1, 3))

    def _write_rows(self, pool, i, bid, row, k, v):
        """One token row per traced ``(bid, row)`` coordinate — the
        decode step, the chain round and the tree commit: ``k``/``v``
        (…, h_kv, d) over the coordinates' leading shape."""
        return self._write(pool, (i, bid, slice(None), row), (i, bid, row),
                           k, v, (k.ndim - 2, k.ndim - 1))

    def _gather_slot(self, pool, i, tables):
        """Layer ``i``'s cached prefix of one slot (``tables`` (nb,)) or
        of every slot ((S, nb)) as padded ``(…, h_kv, max_s, d)`` views;
        quantized pools dequantize in the gather (the multi-token steps
        are compute-bound; the HBM-bound decode step dequantizes
        in-kernel instead)."""
        def view(n):
            a = pool[n][i][tables]  # (…, nb, h_kv, B, d)
            if self.quantized:
                a = a.astype(jnp.float32) \
                    * pool[n + "_scale"][i][tables][..., None, :, None]
            return jnp.swapaxes(a, -4, -3).reshape(
                *a.shape[:-4], a.shape[-3], self.max_s, a.shape[-1])
        return view("k"), view("v")

    # --- prefill chunk -------------------------------------------------------

    def _prefill_chunk(self, *args):
        # trace-time step-anatomy span: every HLO of the chunk program
        # carries the serve_prefill scope in device traces, monitor on or
        # off; entered once per trace, never touching the stable avals
        with monitor_spans.span("serve_prefill"):
            return self._prefill_chunk_body(*args)

    def _prefill_chunk_body(self, params, pool, table_row, tokens, start,
                            live, key):
        """One chunk of ONE slot's prompt: ``tokens`` (C,) are prompt
        positions [start, start+C) with the first ``live`` valid (the
        final chunk is ragged; pad rows are written but land either
        behind the live frontier — overwritten by decode later — or in
        the dead block). Returns ``(pool, first_token, last_logits)``;
        the token/logits are meaningful on the LAST chunk only (row
        ``live - 1`` is then the prompt's final token). ``start`` and
        ``live`` are traced: one executable for every chunk of every
        prompt."""
        m, c = self._math, self.config
        C, B = self.prefill_chunk_size, self.block_size
        params = m.shard(params)
        start = jnp.asarray(start, jnp.int32)
        live = jnp.asarray(live, jnp.int32)

        x = m.embed(params, tokens[None])  # (1, C, H)
        pos = start + jnp.arange(C, dtype=jnp.int32)
        x = x + _pos_rows(params, pos)[None]

        # the chunk's target blocks: C/B table entries from start/B on
        # (chunks are block-aligned: start is always a B-multiple — the
        # scheduler resumes at the shared-prefix frontier, a whole
        # number of blocks — and C is a B-multiple); blocks with no
        # live token redirect to the dead block so the ragged tail
        # cannot touch another slot's memory. Earlier table entries
        # (a shared prefix) are READ via the gather below, never
        # written: the copy-on-write discipline in one index bound
        nblk = C // B
        ids = jax.lax.dynamic_slice(table_row.astype(jnp.int32),
                                    (start // B,), (nblk,))
        blk_live = (jnp.arange(nblk, dtype=jnp.int32) * B) < live
        ids = jnp.where(blk_live, ids, DEAD_BLOCK)

        js = jnp.arange(self.max_s, dtype=jnp.int32)
        mask = js[None, None, None, :] <= pos[None, None, :, None]

        def attend(i, q, k, v):  # (1, C, heads, d)
            nonlocal pool
            # chunk k/v → (C/B, h_kv, B, d) block scatter at traced ids
            kb, vb = (a[0].reshape(nblk, B, *a.shape[2:])
                      .transpose(0, 2, 1, 3) for a in (k, v))
            pool = self._write_blocks(pool, i, ids, kb, vb)
            # prefix attention: chunk queries × the slot's gathered
            # padded cache (chunk rows included — causal within the
            # chunk falls out of the same mask)
            return cached_attention(
                q, *self._gather_slot(pool, i, table_row), mask)
        x = run_layers(m, params, x, attend)
        last = jax.lax.dynamic_slice(
            x, (jnp.int32(0), live - 1, jnp.int32(0)),
            (1, 1, c.hidden_size))
        logits = m.unembed(params, last)[:, 0]  # (1, V)
        return pool, m.sample(logits, key)[0], logits[0]

    # --- decode step ---------------------------------------------------------

    def _decode_step(self, *args):
        # one span per TRACE (not per token), as above
        with monitor_spans.span("serve_decode"):
            return self._decode_step_body(*args)

    def _decode_step_body(self, params, pool, tables, tokens, lengths, key):
        """One token for EVERY slot: ``tokens`` (S,) are each slot's
        incoming sampled tokens, ``lengths`` (S,) the live rows INCLUDING
        them (0 = dead slot: write lands in the dead block, attention
        output zeros, sampled value ignored by the host). Returns
        ``(pool, next_tokens, logits)``. Avals are churn-independent:
        compiled exactly once."""
        m, B = self._math, self.block_size
        params = m.shard(params)
        lengths = lengths.astype(jnp.int32)
        pos = jnp.maximum(lengths - 1, 0)  # the incoming token's position
        x = m.embed(params, tokens[:, None])
        x = x + _pos_rows(params, pos)[:, None]
        tables = tables.astype(jnp.int32)
        bid = jnp.take_along_axis(tables, (pos // B)[:, None], axis=1)[:, 0]
        # dead slots (lengths == 0) write to the dead block NO MATTER what
        # their table row says: a slot mid-prefill is dead for decode but
        # its table already names real blocks — an unredirected write
        # would corrupt its own freshly prefilled cache
        bid = jnp.where(lengths > 0, bid, DEAD_BLOCK)
        row = pos % B
        rel_hook = getattr(self.model, "decode_rel_bias", None)
        rel_bias = None if rel_hook is None else rel_hook(params)

        def attend(i, q, k, v):  # (S, 1, heads, d)
            nonlocal pool
            # per-slot (block, row) scatter into the DONATED pool, BEFORE
            # attention so the token attends to itself (through the
            # contiguous cache's (S, h_kv, 1, d) row layout: the program
            # a plain engine always traced)
            pool = self._write_rows(pool, i, bid, row,
                                    k.transpose(0, 2, 1, 3)[:, :, 0],
                                    v.transpose(0, 2, 1, 3)[:, :, 0])
            # the paged decode-attention kernel over the whole pool: block
            # tables, length masking and the quantized pools' scale
            # indirection (dequantized in-VMEM) live inside it
            scales = {n: a[i] for n, a in pool.items() if n.endswith("scale")}
            return decode_attention(q[:, 0], pool["k"][i], pool["v"][i],
                                    lengths, bias=rel_bias,
                                    block_tables=tables, **scales)[:, None]
        x = run_layers(m, params, x, attend)
        logits = m.unembed(params, x)[:, 0]  # (S, V)
        return pool, m.sample(logits, key), logits

    # --- speculative round ---------------------------------------------------

    def _spec_step(self, *args):
        with monitor_spans.span("serve_spec"):
            return self._spec_step_body(*args)

    def _spec_step_body(self, params, pool, tables, tokens, lengths,
                        drafted, key):
        """One speculative round for EVERY slot at once: ``tokens``
        (S, k+1) are each slot's pending sampled token followed by its k
        drafted continuations, ``lengths`` (S,) the live rows INCLUDING
        the pending token (0 = dead slot: writes land in the dead block,
        outputs ignored by the host), ``drafted`` (S, k) the draft ids.
        All k+1 positions are scored in one multi-token step (the
        chunked-prefill attention shape at chunk = k+1, riding the same
        gathered-cache formulation), their k/v land in the slots' pool
        blocks past the live frontier (the scheduler pre-allocated
        them), and the fused verify tail emits per-slot ``(accept_len,
        next_token)``. Rows past each slot's accepted frontier hold
        rejected-draft k/v — the scheduler rewinds tables/lengths to the
        frontier (contents-only mutation; this program never retraces).
        Returns ``(pool, accept_lens (S,), next_tokens (S,))``."""
        m, B = self._math, self.block_size
        params = m.shard(params)
        lengths = lengths.astype(jnp.int32)
        base = jnp.maximum(lengths - 1, 0)
        pos = base[:, None] + jnp.arange(tokens.shape[1],
                                         dtype=jnp.int32)[None, :]
        x = m.embed(params, tokens)  # (S, K1, H)
        x = x + _pos_rows(params, pos)
        tables = tables.astype(jnp.int32)
        bid = jnp.take_along_axis(tables, pos // B, axis=1)  # (S, K1)
        # dead slots write to the dead block NO MATTER what their table
        # row says (same redirect as the decode step)
        bid = jnp.where(lengths[:, None] > 0, bid, DEAD_BLOCK)
        row = pos % B
        js = jnp.arange(self.max_s, dtype=jnp.int32)
        # prefix-causal per drafted row: row j of slot i sees keys
        # [0, base_i + j] — broadcastable over (S, h_kv, group, K1, max_s)
        mask = js[None, None, None, None, :] <= pos[:, None, None, :, None]

        def attend(i, q, k, v):  # (S, K1, heads, d)
            nonlocal pool
            # (S, K1) rows scattered at traced (block, row) coordinates,
            # then K1 queries per slot × the slot's gathered padded cache
            pool = self._write_rows(pool, i, bid, row, k, v)
            return cached_attention(
                q, *self._gather_slot(pool, i, tables), mask)
        x = run_layers(m, params, x, attend)
        a, nxt = m.verify(m.unembed(params, x), drafted, key)  # (S, K1, V)
        return pool, a, nxt

    # --- tree speculative round ----------------------------------------------

    def _tree_step(self, *args):
        with monitor_spans.span("serve_spec_tree"):
            return self._tree_step_body(*args)

    def _tree_step_body(self, params, pool, tables, tokens, lengths,
                        parents, anc, levels, key):
        """One TREE speculative round for EVERY slot at once: ``tokens``
        (S, N+1) are each slot's pending sampled token (the root, column
        0) plus its N drafted tree-node tokens, ``parents``/``anc`` the
        :class:`~apex_tpu.spec.tree.DraftTree` operands tiled over the
        slot array, ``levels`` a ``(depth+1,)`` iota whose SHAPE carries
        the static depth. Unlike the chain round nothing is scattered
        into the pool before the verdict — sibling nodes SHARE positions,
        so a pre-write would collide; each node instead attends the
        committed cache rows (``js < base``) plus its own root path via
        the ``anc`` tree-attention mask under ONE softmax, the fused
        tree-verify tail picks the deepest accepted path, and only the
        WINNING path's k/v land in the slots' pool blocks (level ``l`` at
        row ``base + l``; levels past ``accept_len`` — and dead slots —
        redirect to the dead block). The scheduler then just commits the
        emitted tokens: no rejected rows ever touched the pool, so the
        rewind is pure host bookkeeping. Returns ``(pool, accept_lens
        (S,), j_star (S,), next_tokens (S,))`` — one executable per
        static ``(N+1, depth+1)``."""
        m, B = self._math, self.block_size
        params = m.shard(params)
        lengths = lengths.astype(jnp.int32)
        base = jnp.maximum(lengths - 1, 0)
        depth_vec = jnp.sum(anc.astype(jnp.int32), axis=-1) - 1  # (S, N1)
        x = m.embed(params, tokens)  # (S, N1, H)
        # siblings SHARE positions
        x = x + _pos_rows(params, base[:, None] + depth_vec)
        tables = tables.astype(jnp.int32)
        js = jnp.arange(self.max_s, dtype=jnp.int32)
        # committed rows only — the root's own k/v rides the TREE part
        # (node 0), not the cache, until the verdict commits it
        cache_mask = js[None, None, None, None, :] \
            < base[:, None, None, None, None]
        tree_mask = (anc != 0)[:, None, None]  # (S, 1, 1, N1, N1)
        tks, tvs = [], []

        def attend(i, q, k, v):  # (S, N1, heads, d)
            tks.append(k)
            tvs.append(v)
            # N1 queries per slot × the slot's gathered padded cache —
            # the chain round's gather, minus the pre-verdict scatter —
            # and × the round's own nodes, under ONE softmax
            return cached_attention(
                q, *self._gather_slot(pool, i, tables), cache_mask,
                tree=(k, v, tree_mask))
        x = run_layers(m, params, x, attend)
        a, j_star, nxt = m.verify_tree(m.unembed(params, x), tokens,
                                       parents, anc, key)  # (S, N1, V)
        # commit the winning path: level l of j_star's root path (root =
        # level 0 = the pending token) lands at pool row base + l; levels
        # past accept_len — and dead slots — redirect to the dead block
        lvl = winning_path_levels(anc, depth_vec, j_star, levels)
        wpos = base[:, None] + levels[None, :]  # (S, depth+1)
        valid = (levels[None, :] <= a[:, None]) & (lengths[:, None] > 0)
        bid = jnp.take_along_axis(tables, wpos // B, axis=1)
        bid = jnp.where(valid, bid, DEAD_BLOCK)
        row = wpos % B
        for i, kv in enumerate(zip(tks, tvs)):
            sel_k, sel_v = (jnp.einsum("bln,bnhd->blhd", lvl.astype(t.dtype),
                                       t) for t in kv)
            pool = self._write_rows(pool, i, bid, row, sel_k, sel_v)
        return pool, a, j_star, nxt

    # --- the serving loop ----------------------------------------------------

    def make_scheduler(self, *, prefix_cache: bool = True,
                       prefix_capacity_blocks: Optional[int] = None,
                       policy: Optional[SLOPolicy] = None) -> Scheduler:
        """A fresh scheduler + allocator matching this engine's pool.

        ``prefix_cache=True`` (the default) attaches a
        :class:`~apex_tpu.serving.kv_blocks.PrefixCache` over the same
        allocator — full prompt blocks are shared copy-on-write across
        requests and survive them as reclaimable warm capacity.
        ``policy`` injects an :class:`~apex_tpu.serving.scheduler.
        SLOPolicy` (one is created by default) for SLO-aware dispatch
        when telemetry is attached."""
        alloc = BlockAllocator(self.num_blocks)
        cache = (PrefixCache(alloc, self.block_size,
                             capacity_blocks=prefix_capacity_blocks)
                 if prefix_cache else None)
        return Scheduler(
            num_slots=self.num_slots, block_size=self.block_size,
            max_blocks_per_slot=self.max_blocks_per_slot,
            allocator=alloc, prefill_chunk=self.prefill_chunk_size,
            prefix_cache=cache,
            policy=policy if policy is not None else SLOPolicy())

    def serve(self, params, requests: List[Request], *,
              key: Optional[jax.Array] = None,
              clock: Optional[Callable[[], float]] = None,
              scheduler: Optional[Scheduler] = None,
              telemetry=None, draft=None, adaptive=None,
              pool=None) -> List[Request]:
        """Run ``requests`` to completion; returns them in completion
        order with tokens and latency stamps filled in.

        Each loop iteration runs at most ONE prefill chunk and ONE
        decode step over the whole slot array — admission and prefill
        interleave with decode instead of stalling it. ``clock`` (a
        monotonically advancing ``() -> seconds`` callable, default
        ``time.perf_counter``) drives arrival replay and the latency
        stamps; requests whose ``arrival_s`` is in the future are held
        until the clock passes it. ``scheduler`` injects a pre-built
        scheduler (tests script churn through it).

        ``telemetry`` attaches a :class:`~apex_tpu.serving.telemetry.
        ServeTelemetry` — request lifecycle events, streaming latency
        histograms, periodic ``serve_window`` records, and the anomaly
        layer, all host-side and outside the jitted steps (the
        zero-recompile contract holds with telemetry on). When the
        monitor registry is enabled and no tracker is passed, a default
        one is attached so an instrumented process gets request traces
        for free; pass ``telemetry=False`` to suppress even that (timed
        baseline runs must not pay emit costs a comparison leg does
        not); with monitoring off and no tracker, every hook site is a
        single ``is None`` test.

        ``draft`` attaches a :class:`~apex_tpu.spec.drafter.Drafter`
        for speculative serving: spec rounds replace plain decode steps
        whenever every decoding slot has k+1 rows of headroom (near the
        row cap the loop falls back to the plain step — a host-side
        choice, never a retrace), interleaving with chunked prefill
        exactly as decode does. Greedy output stays token-identical to
        ``draft=None`` across arbitrary churn; acceptance is accounted
        in ``last_stats`` and per-round ``spec`` lifecycle events.

        A TREE drafter (``is_tree_drafter(draft)``: ``propose_tree``
        plus static ``depth``/``branching``) upgrades the round to the
        tree-verify step; per round the loop degrades tree → chain →
        plain on row or drafter-pool headroom (every rung is a
        pre-compiled program — the ladder never stalls and never
        retraces). A :class:`~apex_tpu.spec.tree.PagedModelDrafter` is
        bound to the scheduler's allocator here, so its KV blocks live
        in THIS pool's accounting. ``adaptive`` (an
        :class:`~apex_tpu.spec.tree.AdaptiveSpecController`) re-picks
        the round's (depth, branching) from its static choice set.

        ``pool`` injects a pre-populated block pool (the disaggregated
        decode role: :func:`~apex_tpu.serving.disagg.ingest_handoff`
        streamed prefilled KV blocks into it); it must have been
        created by THIS engine's :meth:`init_pool` and be paired with
        the ``scheduler`` whose allocator/prefix cache own its live
        blocks. Default: a fresh zeroed pool."""
        if self.temperature > 0 and key is None:
            raise ValueError("temperature > 0 serving requires a key")
        if draft is not None:
            if getattr(self.model, "decode_rel_bias", None) is not None:
                # the spec round's k+1-row scoring does not thread the
                # bucketed relative bias the plain decode step applies;
                # verifying against unbiased spec logits would silently
                # break the token-identical contract (same composition
                # guard as kv_dtype='int8')
                raise ValueError(
                    "serve(draft=...) cannot speculate for a model "
                    "with a decode relative-position bias (the spec "
                    "verify step does not carry the bucketed bias) — "
                    "serve this model with draft=None")
            if self.tp > 1 and self.temperature > 0:
                raise ValueError(
                    "serve(draft=...) with temperature="
                    f"{self.temperature} is unsupported under plan.tp="
                    f"{self.tp}: the sharded verify tail composes the "
                    "greedy argmax across shards but does not carry "
                    "the rejection-sampling draw — serve greedy "
                    "(temperature=0.0) or with plan.tp=1")
            from apex_tpu.spec.drafter import validate_drafter
            from apex_tpu.spec.tree import is_tree_drafter
            if is_tree_drafter(draft) and self.tp > 1:
                raise ValueError(
                    f"serve(draft=<tree drafter>) is unsupported under "
                    f"plan.tp={self.tp}: the tree-verify tail has no "
                    f"psum-composed form — serve tree drafts at tp=1, or "
                    f"use a chain drafter (which verifies under tp)")
            # eager, knob-naming validation: vocab/block_size/k/cache
            # bounds fail HERE, not as an XLA error three rounds in.
            # max_s rows suffice for the drafter: spec rounds only run
            # with k+1 rows of slot headroom (the loop falls back to
            # plain decode near the cap), so a drafter context never
            # exceeds max_s - k tokens
            validate_drafter(draft, self.config, needed_rows=self.max_s,
                             block_size=self.block_size)
        if adaptive is not None:
            from apex_tpu.spec.tree import is_tree_drafter
            if draft is None:
                raise ValueError(
                    "serve(adaptive=...) needs a drafter: the controller "
                    "picks the DRAFT shape per round — pass draft= a "
                    "tree drafter alongside it")
            if not is_tree_drafter(draft):
                raise ValueError(
                    "serve(adaptive=...) needs a TREE drafter (one with "
                    "propose_tree + static depth/branching): the "
                    "controller's choices are (depth, branching) tree "
                    "shapes — NGramTreeDrafter / PagedModelDrafter")
            for dd, _ in adaptive.choices:
                if dd > draft.depth:
                    raise ValueError(
                        f"adaptive choice set reaches depth {dd} but the "
                        f"drafter's static depth is {draft.depth} — the "
                        f"drafter cannot draft deeper than it was built "
                        f"for; shrink the choice set or deepen the "
                        f"drafter")
        if key is None:  # greedy: the key operand is ignored but keeps
            # the step signature (and avals) fixed
            key = jax.random.PRNGKey(0)  # apexlint: disable=APX502
        wall = clock is None
        clock = time.perf_counter if clock is None else clock
        t0 = clock()
        now = lambda: clock() - t0  # noqa: E731
        sched = scheduler if scheduler is not None else self.make_scheduler()
        if draft is not None and hasattr(draft, "bind"):
            # a paged drafter joins THIS scheduler's block economy: its
            # KV blocks come from the same allocator/refcount ledger the
            # target streams use (check_accounting() covers them), and
            # bind wires scheduler.draft_owner so preemption/finish
            # evict drafter blocks through the same path. Re-validate
            # after: bind sets cache_rows (the drafter-geometry cap),
            # which the pre-bind pass could not see
            from apex_tpu.spec.drafter import validate_drafter
            draft.bind(sched, block_size=self.block_size)
            validate_drafter(draft, self.config, needed_rows=self.max_s,
                             block_size=self.block_size)
        tel = telemetry
        if tel is False:  # explicit opt-out beats auto-attachment AND
            # any tracker a reused scheduler still carries — a timed
            # baseline must not fire scheduler-side hooks either
            tel = None
            sched.telemetry = None
        elif tel is None and sched.telemetry is not None:
            # a tracker attached at Scheduler construction is the
            # caller's choice: adopt it fully (engine-side hooks +
            # windows too) instead of shadowing it with an auto one
            tel = sched.telemetry
        elif tel is None and monitor_registry.enabled():
            # an instrumented process gets request traces for free; the
            # auto-attached tracker claims OK only on real hardware
            # (same convention as every bench record)
            backend = jax.default_backend()
            tel = (ServeTelemetry(slots=self.num_slots)
                   if backend == "tpu" else ServeTelemetry(
                       slots=self.num_slots, status="SKIP",
                       reason=f"auto-attached serve telemetry on "
                              f"{backend}: serving windows are TPU "
                              f"measurements"))
        if tel is not None:
            sched.telemetry = tel
            # stamp the pool-quantization knob so the serve record
            # names the pool it measured (absent on float pools)
            tel.kv_dtype = self.kv_dtype
        for r in requests:
            if tel is not None:
                r.submit_s = now()
                tel.on_submit(r, r.submit_s)
            sched.submit(r)
        if self.tp > 1:
            # keep the caller's replicated tree as the hot-swap aval
            # reference; the steps consume the sharded (tp,)-leading
            # copy placed once here (same jit cache across serve calls)
            self._swap_ref = params
            params = self._prepare_params(params)
        # a caller-provided pool must ride with ITS scheduler (the
        # disaggregated decode role: blocks ingested from a prefill
        # engine live in the pool AND in the scheduler's prefix cache /
        # allocator — one without the other would serve garbage rows)
        if pool is None:
            pool = self.init_pool()
            if self.tp == 1:
                # commit the fresh pool beside the params: every step
                # returns a COMMITTED pool whenever the params are
                # committed (a checkpoint restore, a trainer's mesh
                # output, an explicit device_put), and a pool that went
                # uncommitted -> committed between the first and second
                # dispatch would be a second prefill_chunk executable
                pool = jax.device_put(
                    pool, jax.tree.leaves(params)[0].sharding)
        stats = ServeStats()
        # per-transition lifecycle records skip the per-line sink flush
        # inside the loop (one flush at the end) — the dominant cost of
        # an emit at token rates; see ServeTelemetry's overhead budget
        reg = monitor_registry.get_registry()
        flush_scope = (reg.buffered() if reg is not None and tel is not None
                       else contextlib.nullcontext())
        if tel is not None:
            # prime the first window's clock BEFORE any work: the first
            # iteration's tokens must not be divided by a window that
            # started after they were produced
            tel.maybe_window(now(), sched)
        try:
            # the serve-CALL trace context: engine-level records with no
            # per-request id (spans, serve_windows, rid -1 straggler /
            # swap events, the final serve record) share one ambient
            # serve-scoped id; per-request events carry their own
            # explicit ids, which win over the ambient one
            with flush_scope, \
                    monitor_trace.trace_context(
                        monitor_trace.new_trace_id("serve")):
                self._serve_loop(params, key, sched, tel, stats, now,
                                 wall, pool, draft, adaptive)
        finally:
            # a deferred swap this run never applied does NOT survive
            # into a later serve() call — clean return OR mid-run
            # exception — silently hot-swapping a stale checkpoint into
            # an unrelated run (or raising its aval error there) would
            # be worse than dropping it; stats.swaps==0 is the tell
            self._pending_swap = None
        self.last_stats = stats
        return sched.completed

    def _serve_loop(self, params, key, sched, tel, stats, now, wall, pool,
                    draft=None, adaptive=None):
        nstep = 0
        policy = sched.policy
        K = draft.k if draft is not None else 0
        if draft is not None:
            from apex_tpu.spec.tree import draft_tree, is_tree_drafter
            tree_capable = is_tree_drafter(draft)
        else:
            tree_capable = False
        ncompleted = len(sched.completed)
        while not sched.idle():
            # weight hot-swap lands HERE, between dispatch steps: a
            # contents-only params replacement (avals validated), so
            # neither jitted program retraces and in-flight requests
            # continue on their existing cache
            params = self._maybe_swap(params, nstep, tel, stats, now())
            sched.admit(now())
            did_work = False
            # the SLO policy widens the prefill share under queue
            # buildup: up to `prefill_share` chunks this iteration —
            # the SAME compiled program run more often, never a new one
            share = policy.prefill_share if policy is not None else 1
            for _ in range(share):
                work = sched.next_prefill(now())
                if work is None:
                    break
                sched.note_step(nstep)
                t_dispatch = now()
                pool, tok, _ = self.prefill_chunk(
                    params, pool,
                    jnp.asarray(sched.tables.row(work.slot)),
                    jnp.asarray(work.tokens),
                    jnp.int32(work.start), jnp.int32(work.live),
                    jax.random.fold_in(key, nstep))
                tok = int(tok)  # blocks until the chunk really ran
                if tel is not None:
                    tel.on_prefill_chunk(
                        work.rid, work.slot, now() - t_dispatch,
                        sched.blocks_held(work.slot), nstep, now())
                nstep += 1
                stats.prefill_chunks += 1
                sched.note_prefill(work, tok, now())
                did_work = True
            # the speculative mode ladder, re-picked per round: tree →
            # chain → plain, stepping DOWN on row headroom (every rung
            # is a pre-compiled program — a host-side choice, never a
            # retrace, never a stall). The tree rung needs depth+1 rows
            # of slot headroom, the chain rung k+1
            mode, shape = "plain", None
            if draft is not None:
                dec = sched.decoding_slots()
                if dec and tree_capable:
                    shape = (adaptive.round_shape(
                        [sched.slot_rid(i) for i in dec])
                        if adaptive is not None
                        else (draft.depth, draft.branching))
                    if all(sched.slot_length(i) + shape[0] + 1
                           <= self.max_s for i in dec):
                        mode = "tree"
                if mode == "plain" and dec and all(
                        sched.slot_length(i) + K + 1 <= self.max_s
                        for i in dec):
                    mode = "chain"
                    if tree_capable:
                        stats.spec_degraded += 1
            lookahead = (shape[0] if mode == "tree"
                         else K if mode == "chain" else 0)
            batch = sched.decode_batch(now(), lookahead=lookahead)
            # drafter-pool headroom comes AFTER decode_batch — it can
            # preempt (changing both the live set and the free count).
            # A short pool degrades the round down the same ladder:
            # blocks already reserved for the wider lookahead stay
            # assigned to their slots (reused as the stream grows —
            # never leaked), and the drafter allocates nothing
            if batch is not None and mode != "plain" \
                    and hasattr(draft, "round_blocks_needed"):
                while mode != "plain":
                    d_rows = shape[0] if mode == "tree" else K
                    need = sum(
                        draft.round_blocks_needed(
                            sched.slot_rid(i),
                            len(sched.slot_context(i)), depth=d_rows)
                        for i in sched.decoding_slots())
                    if need <= sched.allocator.num_free:
                        break
                    mode = "chain" if mode == "tree" else "plain"
                    stats.spec_degraded += 1
            if batch is not None and mode == "tree":
                toks, lens = batch
                depth, branching = shape
                tree = draft_tree(branching, depth)
                live = [i for i in range(self.num_slots) if lens[i] > 0]
                node_toks = np.zeros((self.num_slots, tree.num_nodes),
                                     np.int32)
                rids = {}
                for i in live:
                    rids[i] = sched.slot_rid(i)
                    node_toks[i] = draft.propose_tree(
                        rids[i], sched.slot_context(i),
                        shape=(depth, branching))
                tok_mat = np.zeros((self.num_slots, tree.n1), np.int32)
                tok_mat[:, 0] = toks
                tok_mat[:, 1:] = node_toks
                # topology operands ship as CONTENTS (uniform over the
                # slot array, dead rows ignored by the host): the
                # executable is pinned per (num_nodes+1, depth+1)
                parents, anc = tree.operands(self.num_slots)
                levels = np.arange(depth + 1, dtype=np.int32)
                sched.note_step(nstep)
                t_dispatch = now()
                pool, acc, jst, nxt = self.spec_tree_step(
                    params, pool, jnp.asarray(sched.tables.asarray()),
                    jnp.asarray(tok_mat), jnp.asarray(lens),
                    jnp.asarray(parents), jnp.asarray(anc),
                    jnp.asarray(levels), jax.random.fold_in(key, nstep))
                acc = np.asarray(acc)  # blocks: the round really ran
                jst = np.asarray(jst)
                nxt = np.asarray(nxt)
                round_dur = now() - t_dispatch
                if tel is not None:
                    tel.on_decode_step(round_dur, len(live), nstep, now())
                nstep += 1
                stats.decode_steps += 1
                stats.spec_rounds += 1
                stats.tree_rounds += 1
                stats.occupancy_samples.append(len(live))
                emitted = {}
                for i in live:
                    a = int(acc[i])
                    emitted[i] = tree.path_tokens(node_toks[i], a,
                                                  int(jst[i]), int(nxt[i]))
                    stats.spec_drafted += depth
                    stats.spec_accepted += a
                    stats.spec_nodes += tree.num_nodes
                    stats.spec_slot_rounds += 1
                    if tel is not None:
                        tel.on_spec_round(rids[i], i, a, depth, nstep - 1,
                                          now(), dur_ms=round_dur * 1e3,
                                          nodes=tree.num_nodes,
                                          branching=branching)
                    if adaptive is not None:
                        adaptive.note_round(rids[i], a, depth)
                sched.note_spec_tokens(emitted, now())
                did_work = True
            elif batch is not None and mode == "chain":
                toks, lens = batch
                live = [i for i in range(self.num_slots) if lens[i] > 0]
                # drafts come from the host drafter per stream; the
                # verify operands stay fixed-shape (static k)
                drafted = np.zeros((self.num_slots, K), np.int32)
                rids = {}
                for i in live:
                    rids[i] = sched.slot_rid(i)
                    drafted[i] = draft.propose(rids[i],
                                               sched.slot_context(i))
                tok_mat = np.zeros((self.num_slots, K + 1), np.int32)
                tok_mat[:, 0] = toks
                tok_mat[:, 1:] = drafted
                sched.note_step(nstep)
                t_dispatch = now()
                pool, acc, nxt = self.spec_step(
                    params, pool, jnp.asarray(sched.tables.asarray()),
                    jnp.asarray(tok_mat), jnp.asarray(lens),
                    jnp.asarray(drafted), jax.random.fold_in(key, nstep))
                acc = np.asarray(acc)  # blocks: the round really ran
                nxt = np.asarray(nxt)
                round_dur = now() - t_dispatch
                if tel is not None:
                    tel.on_decode_step(round_dur, len(live),
                                       nstep, now())
                nstep += 1
                stats.decode_steps += 1
                stats.spec_rounds += 1
                stats.occupancy_samples.append(len(live))
                for i in live:
                    a = int(acc[i])
                    stats.spec_drafted += K
                    stats.spec_accepted += a
                    stats.spec_nodes += K
                    stats.spec_slot_rounds += 1
                    if adaptive is not None:
                        # a degraded (chain) round still teaches the
                        # controller — acceptance over k chain rows
                        adaptive.note_round(rids[i], a, K)
                    if tel is not None:
                        # the round's full wall time for EVERY live slot
                        # (concurrent wall time — what a per-request e2e
                        # partition must bill)
                        tel.on_spec_round(rids[i], i, a, K, nstep - 1,
                                          now(), dur_ms=round_dur * 1e3)
                sched.note_spec(drafted, acc, nxt, now())
                did_work = True
            elif batch is not None:
                toks, lens = batch
                ndec = len(sched.decoding_slots())
                sched.note_step(nstep)
                t_dispatch = now()
                pool, sampled, _ = self.decode_step(
                    params, pool, jnp.asarray(sched.tables.asarray()),
                    jnp.asarray(toks), jnp.asarray(lens),
                    jax.random.fold_in(key, nstep))
                sampled = np.asarray(sampled)  # blocks: step really ran
                if tel is not None:
                    tel.on_decode_step(now() - t_dispatch, ndec, nstep,
                                       now())
                nstep += 1
                stats.decode_steps += 1
                stats.occupancy_samples.append(ndec)
                sched.note_decode(sampled, now())
                did_work = True
            if draft is not None and len(sched.completed) > ncompleted:
                # free finished streams' drafter state (caches bounded
                # by CONCURRENT streams, not request history)
                for r in sched.completed[ncompleted:]:
                    draft.release(r.rid)
                    if adaptive is not None:
                        adaptive.release(r.rid)
                ncompleted = len(sched.completed)
            stats.blocks_high_water = max(stats.blocks_high_water,
                                          sched.allocator.num_live)
            if tel is not None:
                if tel.maybe_window(now(), sched) is not None \
                        and policy is not None:
                    # window edge: fold the fresh SLO/anomaly signals
                    # into the dispatch knobs (SLO-aware scheduling)
                    policy.update(tel)
                    pop = getattr(policy, "pop_replan", None)
                    staged = pop() if pop is not None else None
                    if staged is not None:
                        # an online re-plan landed: the aval-stable
                        # knobs (share bound, admission order, SLO
                        # thresholds) are already applied in update();
                        # a spec-shape diff caps the adaptive ladder on
                        # its PRE-COMPILED choice set; aval-changing
                        # knobs ride the event as deferred_knobs —
                        # reported, never applied mid-serve
                        shape = staged.pop("spec_shape", None)
                        if shape is not None and adaptive is not None:
                            adaptive.set_cap(shape)
                        tel.on_replan(nstep, now(), **staged)
            if not did_work and wall:
                # nothing runnable: only future arrivals remain
                time.sleep(1e-4)
        # the final pool outlives the loop for the disaggregated
        # prefill role: export_handoff lifts warm prefix blocks out of
        # it (paired with the scheduler whose cache indexes them)
        self.last_pool = pool
