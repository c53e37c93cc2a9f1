"""GPT: the flagship model — a Megatron-style decoder-only transformer.

Re-design of ``apex/transformer/testing/standalone_gpt.py`` (``ParallelMLP``
:236, ``ParallelAttention`` :285, full GPT stack): vocab-parallel embedding,
N pre-LN blocks of (fused LN → TP attention → residual → fused LN → TP MLP →
residual), final LN, tied unembedding, vocab-parallel cross-entropy.

TPU-first choices:
* activations are (batch, seq, hidden) bf16-able; attention uses the fused
  causal softmax kernel (no 2048 seq cap);
* TP via Column/Row parallel linears (QKV column-sharded by head, output
  row-sharded), runnable at tp_size=1 with zero collectives;
* sequence parallelism optional on the linears (``sequence_parallel``);
* activation remat per block via ``jax.checkpoint`` (``remat=True``);
* dropout keys are explicit (``jax.random``), folded per (layer, op, tp rank).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from apex_tpu.monitor import spans as monitor_spans
from apex_tpu.ops import fused_layer_norm, scaled_upper_triang_masked_softmax
from apex_tpu.ops.attention import flash_attention, seed_from_key
from apex_tpu.transformer import tensor_parallel as tp_lib
from apex_tpu.transformer.tensor_parallel.utils import divide


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304
    max_seq_len: int = 2048
    hidden_size: int = 1024
    ffn_hidden_size: Optional[int] = None  # default 4*hidden
    num_layers: int = 12
    num_heads: int = 16
    # grouped-query attention: fewer kv heads than query heads (None =
    # num_heads, full MHA; 1 = MQA). Beyond the reference — its fmha
    # kernels require equal head counts. Must divide num_heads and be
    # divisible by tp_size.
    num_kv_heads: Optional[int] = None
    tp_size: int = 1
    tp_axis: Optional[str] = "tp"  # None → single-chip, no collectives
    sequence_parallel: bool = False
    # Ring-overlapped TP boundary collectives (ops.collective_matmul): the
    # Column/Row linears and the flash attention projections trade their
    # blocking all-gather/reduce-scatter/psum for compute-overlapped
    # ppermute rings (with SP: ag→matmul and matmul→reduce-scatter;
    # without: overlapped backward psum / matmul→all-reduce). Blocking
    # (False) stays the parity oracle. Requires tp_size >= 2 and the
    # flash attention path; composing with cp is future work.
    tp_overlap: bool = False
    # Pipeline schedule family, consumed by GPTPipeline (pp >= 2):
    # "1f1b" — scanned forward + autodiff backward (interleaved when the
    # pipeline runs virtual chunks); "zb" — zero-bubble split backward
    # (dX on the critical path, dW deferred into a real-items-only sweep;
    # schedules.py has the cost model). overlap_p2p restructures every
    # pipeline tick so the stage-boundary ppermute hop is issued before
    # the stage body it no longer feeds (the PR-5 collective-matmul trick
    # at the pp boundary; with virtual chunks the microbatch count must
    # then divide 2*pp).
    pp_schedule: str = "1f1b"
    overlap_p2p: bool = False
    dropout: float = 0.0
    remat: bool = True
    # "full": recompute the whole block in backward (Megatron
    # CheckpointFunction semantics, minimum memory); "save_attn"/
    # "save_attn_mlp": full-block remat that stores the attention output
    # (/+ mlp hidden) so the re-forward skips those matmuls — NOTE attention
    # *backward* still needs q/k/v, so the qkv projection and flash forward
    # are recomputed regardless and the win is small; "mlp_only": leave the
    # attention half un-rematted (its residuals stay live, ~+2G at
    # GPT-medium/seq1024/b16) and recompute only the MLP half — skips the
    # whole attention re-forward, the measured-fastest policy that still
    # bounds the big (4H) mlp activations.
    remat_policy: str = "full"
    # scan vs unrolled layer loop: scan compiles O(1) in depth (the
    # reference-style module list is inherently "unrolled"); unrolling
    # removes the scan carry's copy/dynamic-slice overhead at the price of
    # depth-proportional compile time — measured on the flagship bench
    # before choosing the default
    scan_layers: bool = True
    dtype: Any = jnp.float32  # param dtype; compute follows inputs/policy
    # "softmax": materialized scores + fused causal softmax (the Megatron
    # path, ``standalone_gpt.py``'s ParallelAttention); "flash": blockwise
    # flash attention — O(s) memory, no seq cap, preferred at long seq;
    # "naive": plain jnp softmax with autodiff-saved probabilities — the
    # stock-JAX reference point benchmarks compare against, never preferred.
    attention_impl: str = "softmax"
    # Mixture-of-experts in the MLP slot (None = dense). The expert FFN
    # width is ``ffn``; experts shard over ``ep_axis`` when run inside
    # shard_map (apex_tpu.parallel.mesh's dedicated ep axis). The router's
    # aux losses enter loss_fn with the coefficients below; aux stats
    # (incl. drop_fraction) surface via loss_fn(..., return_aux=True).
    moe_num_experts: Optional[int] = None
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_coeff: float = 1e-2
    moe_z_coeff: float = 1e-3
    ep_axis: Optional[str] = None
    # Context parallelism: activations (and tokens) are sharded along the
    # SEQUENCE over this mesh axis; attention runs distributed — "ring"
    # (zigzag-sharded ring attention: shard `zigzag_shard(tokens, cp)`
    # over the axis; O(s_local) memory, kv rotates the ring) or "ulysses"
    # (contiguous sharding, two all_to_alls re-shard heads; needs
    # heads % cp == 0). Everything else in the block is position-wise, so
    # the model runs unchanged on the shard; position embeddings follow
    # the layout (zigzag stripes / contiguous) automatically. Composes
    # with tp (+SP), pp, and dp in one mesh.
    cp_axis: Optional[str] = None
    cp_impl: str = "ring"
    # The unified parallelism object (ISSUE 12): pass a ParallelPlan and
    # the loose knobs above (tp_size, sequence_parallel, tp_overlap,
    # pp_schedule, overlap_p2p, cp_axis/ep_axis) are DERIVED from it —
    # one validated source of truth shared with make_mesh and
    # build_schedule. Left None, the loose kwargs construct a shim plan
    # (the deprecated path — no caller breaks), and the parallel
    # cross-field validation below routes through ParallelPlan.validate
    # either way. Model-coupled constraints (flash attention for
    # tp_overlap/cp, head divisibility) stay here: the plan cannot know
    # them.
    plan: Optional[Any] = None

    def __post_init__(self):
        from apex_tpu.plan.parallel_plan import ParallelPlan

        if self.plan is not None:
            p = self.plan
            if not isinstance(p, ParallelPlan):
                p = ParallelPlan.from_json(p)
                object.__setattr__(self, "plan", p)
            # the plan is the single source of truth: a loose parallel
            # kwarg explicitly set to something the plan contradicts is
            # an eager named-knob error, never a silent override
            derived = {"tp_size": p.tp,
                       "sequence_parallel": p.sequence_parallel,
                       "tp_overlap": p.tp_overlap,
                       "pp_schedule": p.pp_schedule,
                       "overlap_p2p": p.overlap_p2p}
            defaults = {"tp_size": 1, "sequence_parallel": False,
                        "tp_overlap": False, "pp_schedule": "1f1b",
                        "overlap_p2p": False}
            for name, want in derived.items():
                got = getattr(self, name)
                if got != defaults[name] and got != want:
                    raise ValueError(
                        f"{name}={got!r} contradicts plan="
                        f"{p.describe()} (which implies {name}="
                        f"{want!r}); pass the knob through the plan, "
                        f"not alongside it")
                object.__setattr__(self, name, want)
            if p.cp > 1 and self.cp_axis is None:
                object.__setattr__(self, "cp_axis", "cp")
            if p.ep > 1 and self.ep_axis is None:
                object.__setattr__(self, "ep_axis", "ep")
        else:
            # the deprecated loose-kwarg shim: every construction owns a
            # plan, and the plan's validator is the one that rejects
            # illegal parallel combos (PlanError is a ValueError)
            object.__setattr__(self, "plan", ParallelPlan.from_model_kwargs(
                tp_size=self.tp_size,
                sequence_parallel=self.sequence_parallel,
                tp_overlap=self.tp_overlap,
                pp_schedule=self.pp_schedule,
                overlap_p2p=self.overlap_p2p))
        if self.moe_num_experts is not None:
            if self.moe_num_experts < 2:
                raise ValueError("moe_num_experts must be >= 2 (None = dense)")
            if self.ffn % self.tp_size:
                raise ValueError(
                    f"MoE with tensor parallelism shards each expert's ffn "
                    f"dim: ffn ({self.ffn}) must be divisible by tp_size "
                    f"({self.tp_size})")
        if self.attention_impl not in ("softmax", "flash", "naive"):
            raise ValueError(
                f"attention_impl must be softmax|flash|naive, got "
                f"{self.attention_impl!r}")
        # pp_schedule legality (and tp_overlap's tp_size >= 2) now live
        # in ParallelPlan.validate — routed through the plan above
        if self.remat_policy not in (
                "full", "save_attn", "save_attn_mlp", "mlp_only"):
            raise ValueError(
                f"remat_policy must be full|save_attn|save_attn_mlp|mlp_only, "
                f"got {self.remat_policy!r}")
        if self.cp_axis is not None:
            if self.cp_impl not in ("ring", "ulysses"):
                raise ValueError(
                    f"cp_impl must be ring|ulysses, got {self.cp_impl!r}")
            if self.attention_impl != "flash":
                raise ValueError(
                    "context parallelism distributes the flash kernel "
                    "family; set attention_impl='flash'")
        if self.tp_overlap:
            if self.tp_axis is None:
                raise ValueError(
                    "tp_overlap needs a bound tp axis; tp_axis=None runs "
                    "the linears without collectives, so the flag would "
                    "silently measure the blocking path — unset "
                    "tp_overlap or name the mesh axis")
            if self.attention_impl != "flash":
                raise ValueError(
                    "tp_overlap rides the flash attention path (the packed "
                    "QKV projection the ring feeds); set "
                    "attention_impl='flash'")
            if self.cp_axis is not None:
                raise ValueError(
                    "tp_overlap does not yet compose with context "
                    "parallelism (the cp attention branch re-shards the "
                    "sequence the rings chunk); run cp with the blocking "
                    "boundary collectives")
        if self.num_kv_heads is not None:
            if self.num_kv_heads < 1:
                raise ValueError(
                    f"num_kv_heads must be >= 1, got {self.num_kv_heads}")
            if self.num_heads % self.num_kv_heads:
                raise ValueError(
                    f"num_kv_heads ({self.num_kv_heads}) must divide "
                    f"num_heads ({self.num_heads})")
            if self.num_kv_heads % self.tp_size:
                raise ValueError(
                    f"num_kv_heads ({self.num_kv_heads}) must be divisible "
                    f"by tp_size ({self.tp_size})")

    @property
    def ffn(self) -> int:
        return self.ffn_hidden_size or 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        return divide(self.hidden_size, self.num_heads)

    @property
    def local_heads(self) -> int:
        return divide(self.num_heads, self.tp_size)

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads if self.num_kv_heads is not None else self.num_heads

    @property
    def local_kv_heads(self) -> int:
        return divide(self.kv_heads, self.tp_size)

    @property
    def qkv_features(self) -> int:
        """Global QKV projection width: h_q + 2*h_kv head groups."""
        return (self.num_heads + 2 * self.kv_heads) * self.head_dim


class GPTModel:
    """Functional GPT. ``init(key)`` → params pytree (per-TP-shard when
    tp_size > 1 — build under ``shard_map`` or shard a replicated init);
    ``loss_fn(params, tokens, targets, key)`` → mean LM loss."""

    def __init__(self, config: GPTConfig):
        c = self.config = config
        axis = c.tp_axis if c.tp_size > 1 else None
        self.axis = axis
        sp = c.sequence_parallel and c.tp_size > 1
        self.sp = sp
        self.moe = c.moe_num_experts is not None
        if self.moe:
            from apex_tpu.transformer.moe import MoEMLP
            self.moe_bank = MoEMLP(c.moe_num_experts, c.hidden_size, c.ffn,
                                   tp_size=c.tp_size)
        self.embedding = tp_lib.VocabParallelEmbedding(
            c.vocab_size, c.hidden_size, tp_size=c.tp_size, axis_name=axis
        )
        # activations are (batch, seq, hidden) → seq_dim=1 for the SP
        # all-gather/reduce-scatter boundaries
        overlap = c.tp_overlap and axis is not None
        self.overlap = overlap
        self.qkv = tp_lib.ColumnParallelLinear(
            c.hidden_size, c.qkv_features, tp_size=c.tp_size, axis_name=axis,
            sequence_parallel=sp, seq_dim=1, overlap_comm=overlap,
        )
        self.attn_out = tp_lib.RowParallelLinear(
            c.hidden_size, c.hidden_size, tp_size=c.tp_size, axis_name=axis,
            sequence_parallel=sp, seq_dim=1, overlap_comm=overlap,
        )
        self.mlp_up = tp_lib.ColumnParallelLinear(
            c.hidden_size, c.ffn, tp_size=c.tp_size, axis_name=axis,
            sequence_parallel=sp, seq_dim=1, overlap_comm=overlap,
        )
        self.mlp_down = tp_lib.RowParallelLinear(
            c.ffn, c.hidden_size, tp_size=c.tp_size, axis_name=axis,
            sequence_parallel=sp, seq_dim=1, overlap_comm=overlap,
        )

    # --- params ---------------------------------------------------------------

    def init(self, key, rank: int = 0):
        c = self.config
        keys = jax.random.split(key, c.num_layers + 2)
        layers = []
        for i in range(c.num_layers):
            k = jax.random.split(keys[i], 4)
            layer = {
                "ln1_w": jnp.ones((c.hidden_size,), c.dtype),
                "ln1_b": jnp.zeros((c.hidden_size,), c.dtype),
                "qkv": self.qkv.init(k[0], rank, c.dtype),
                "attn_out": self.attn_out.init(k[1], rank, c.dtype),
                "ln2_w": jnp.ones((c.hidden_size,), c.dtype),
                "ln2_b": jnp.zeros((c.hidden_size,), c.dtype),
            }
            if self.moe:
                # the FULL expert bank (this tp rank's ffn shard under tp);
                # under expert parallelism shard the leading expert axis of
                # w1/b1/w2/b2 over ep (router replicated) — cf.
                # shard_params_for_tp's pattern
                layer["moe"] = self.moe_bank.init(k[2], rank, c.dtype)
            else:
                layer["mlp_up"] = self.mlp_up.init(k[2], rank, c.dtype)
                layer["mlp_down"] = self.mlp_down.init(k[3], rank, c.dtype)
            layers.append(layer)
        params = {
            "embedding": self.embedding.init(keys[-2], rank, c.dtype),
            "pos_embedding": jax.random.normal(
                keys[-1], (c.max_seq_len, c.hidden_size), c.dtype
            ) * 0.01,
            "layers": jax.tree.map(lambda *xs: jnp.stack(xs), *layers),
            "lnf_w": jnp.ones((c.hidden_size,), c.dtype),
            "lnf_b": jnp.zeros((c.hidden_size,), c.dtype),
        }
        return params

    # --- blocks ---------------------------------------------------------------

    def _cp_positions(self, s_loc):
        """Global position ids of this cp rank's sequence shard: the zigzag
        stripe pair for ring (device r holds stripes (r, 2cp−1−r) of 2·cp —
        `ops.attention.zigzag_indices`), contiguous for ulysses."""
        c = self.config
        cp = jax.lax.axis_size(c.cp_axis)
        if cp * s_loc > c.max_seq_len:
            # out-of-range ids would silently CLAMP in the pos_embedding
            # gather (JAX gather default) — half the sequence training on
            # repeated positions with no error; fail at trace time instead
            # (the dense path fails loudly via the [:s] shape mismatch)
            raise ValueError(
                f"global sequence cp*s_local = {cp}*{s_loc} exceeds "
                f"max_seq_len ({c.max_seq_len}); raise max_seq_len")
        rank = jax.lax.axis_index(c.cp_axis)
        if c.cp_impl == "ring":
            st = s_loc // 2
            return jnp.concatenate([
                rank * st + jnp.arange(st),
                (2 * cp - 1 - rank) * st + jnp.arange(st)])
        return rank * s_loc + jnp.arange(s_loc)

    def _attention(self, p, x, key):
        c = self.config
        h, d = c.local_heads, c.head_dim
        hkv = c.local_kv_heads
        use_flash = c.attention_impl == "flash"
        drop = c.dropout if (c.dropout > 0 and key is not None) else 0.0
        seed = None
        if drop > 0 and use_flash:
            # in-kernel probs dropout seed: per (layer, op-slot 0) from the
            # caller's folded key, plus the tp rank — each rank's heads
            # draw decorrelated masks (Megatron's model-parallel RNG
            # stream for attention dropout, tensor_parallel/random.py)
            k0 = jax.random.fold_in(key, 0)
            if self.axis is not None:
                k0 = jax.random.fold_in(k0, jax.lax.axis_index(self.axis))
            seed = seed_from_key(k0)
        if use_flash and self.overlap:
            return self._attention_tp_overlap(p, x, drop, seed)
        if use_flash:
            xg = self.qkv.gather_input(x)             # (b, s, H) full seq
            s_len = xg.shape[1]
            from apex_tpu.amp.lists import apply_op_rules
            from apex_tpu.ops import _backend
            from apex_tpu.ops.attention import (bshd_kernel_ok,
                                                flash_auto_crossover,
                                                fused_qkv_attention,
                                                packed_kernel_ok)
            # the O1 per-op cast applies before the kernel-eligibility
            # gate — an fp16-casting policy must land on the XLA path
            # (Mosaic has no f16), so the gate sees the POST-cast dtype
            xc, w_qkv, b_qkv, w_out = apply_op_rules(
                "attention", xg, p["qkv"]["weight"],
                p["qkv"].get("bias"), p["attn_out"]["weight"])
            fused_ok = (
                c.cp_axis is None  # cp: attention is distributed below
                and "bias" in p["qkv"]
                and packed_kernel_ok(s_len, h, hkv, d, xc.dtype)
                and (s_len >= flash_auto_crossover(d)
                     or _backend.interpret_forced())
                and _backend.choose_impl("auto", True) == "pallas"
            )
            if fused_ok:
                # The zero-layout-copy path: packed QKV GEMM → flash
                # kernels reading head windows straight from the packed
                # buffer → output GEMM, all plain 2D contractions with a
                # hand-written VJP (see ops.attention.fused_qkv_attention
                # — kills the ~4.5 GB/step of XLA layout-conversion copies
                # the composed formulation paid, PERF.md r3). Heads of 128,
                # and heads of 64 two to a lane tile (an even local count,
                # no grouped kv: gpt2-medium's 16).
                y = fused_qkv_attention(
                    xc, w_qkv, b_qkv, w_out, None, seed, None, h, hkv, d,
                    1.0 / float(d) ** 0.5, True, drop)
                y = self.attn_out.reduce_output(y)
                if "bias" in p["attn_out"]:
                    y = y + p["attn_out"]["bias"]
                return y
            if (c.cp_axis is None
                    and not bshd_kernel_ok(s_len, s_len, h, d, xc.dtype)
                    and d == 64 and s_len % 128 == 0
                    and xc.dtype != jnp.float16
                    and (s_len >= flash_auto_crossover(d)
                         or _backend.interpret_forced())
                    and _backend.choose_impl("auto", True) == "pallas"):
                # what is left of d=64 after the pair rule above — a qkv
                # projection without bias, an odd local head count (tp),
                # grouped kv: the folded bshd layout cannot take 64-wide
                # blocks, the bh-flat kernels can, so these keep the
                # head-batched route (its layout copies and the dq | dkv
                # split backward: 13.4 + 13.5 ms a step at gpt2-medium's
                # shape, PERF.md §6, PR 42) and do not lose the kernel
                qkv4 = self.qkv.headwise(p["qkv"], x, h + 2 * hkv)
                q4 = qkv4[:, :h]
                k4 = qkv4[:, h:h + hkv]
                v4 = qkv4[:, h + hkv:]
                ctx4 = flash_attention(q4, k4, v4, causal=True,
                                       dropout_rate=drop,
                                       dropout_seed=seed)
                return self.attn_out.headwise(p["attn_out"], ctx4)
            # Below the kernel crossover (or bias-less layers): seq-major
            # (bshd) einsums + the flash entry's XLA/Pallas dispatch. The
            # (b, s, h, d) layout is the GEMM's natural output, so this
            # path too avoids the old head-batched formulation's copies.
            from apex_tpu.ops.attention import (bshd_output_projection,
                                                bshd_qkv_projection)
            q, k, v = bshd_qkv_projection(
                xg, p["qkv"]["weight"], p["qkv"].get("bias"), h, hkv, d)
            if c.cp_axis is not None:
                # context parallelism: q/k/v cover this device's sequence
                # shard; attention distributes over the cp axis — ring (kv
                # shards rotate, zigzag-balanced causal) or Ulysses (two
                # all_to_alls trade seq for head sharding). The op-rules
                # cast that flash_attention applies internally is applied
                # here instead (ring/ulysses take q/k/v directly).
                from apex_tpu.ops.attention import (ring_attention,
                                                    ulysses_attention)
                q, k, v = apply_op_rules("attention", q, k, v)
                if c.cp_impl == "ulysses":
                    ctx = ulysses_attention(q, k, v, axis_name=c.cp_axis,
                                            causal=True,
                                            dropout_rate=drop,
                                            dropout_seed=seed)
                elif bshd_kernel_ok(q.shape[1] // 2, q.shape[1] // 2, h,
                                    d, q.dtype):
                    # ring rides the seq-major kernels directly (r4 late):
                    # the stripe pieces read the projection GEMMs' layout
                    # with zero transposes per ring step
                    ctx = ring_attention(q, k, v, axis_name=c.cp_axis,
                                         causal=True, layout="bshd",
                                         dropout_rate=drop,
                                         dropout_seed=seed)
                else:
                    # bh-flat fallback (d=64-class shapes the folded bshd
                    # tiling can't express): transpose round trip per layer
                    b_sz, s_loc = q.shape[0], q.shape[1]
                    to_bh = lambda z: z.transpose(0, 2, 1, 3).reshape(  # noqa: E731
                        b_sz * z.shape[2], s_loc, d)
                    of = ring_attention(to_bh(q), to_bh(k), to_bh(v),
                                        axis_name=c.cp_axis, causal=True,
                                        dropout_rate=drop,
                                        dropout_seed=seed)
                    ctx = of.reshape(b_sz, h, s_loc, d).transpose(0, 2, 1, 3)
            else:
                ctx = flash_attention(q, k, v, causal=True, layout="bshd",
                                      dropout_rate=drop, dropout_seed=seed)
            y = bshd_output_projection(ctx, p["attn_out"]["weight"], h, d)
            y = self.attn_out.reduce_output(y)
            if "bias" in p["attn_out"]:
                y = y + p["attn_out"]["bias"]
            return y

        # Head-batched QKV projection (ColumnParallelLinear.headwise):
        # q/k/v come out (b, h, s, d) straight from the MXU (the
        # materialized-scores paths below want that layout anyway).
        # Local output features stay packed (q-heads | k-heads | v-heads) —
        # grouped, heads within each group (Megatron packs (h, 3d) because
        # its *global* qkv weight must shard per-head across tp ranks; here
        # params are built per-rank, so the grouped order is free). With
        # grouped-query attention (num_kv_heads < num_heads) the k/v groups
        # are simply narrower.
        qkv = self.qkv.headwise(p["qkv"], x, h + 2 * hkv)  # (b, h+2hkv, s, d)
        b, s = qkv.shape[0], qkv.shape[2]
        # (b, h, s, d) / (b, hkv, s, d)
        q = qkv[:, :h]
        k = qkv[:, h:h + hkv]
        v = qkv[:, h + hkv:]
        if hkv < h:
            # the materialized-scores paths below broadcast kv heads
            k = jnp.repeat(k, h // hkv, axis=1)
            v = jnp.repeat(v, h // hkv, axis=1)
        if c.attention_impl == "naive":
            # stock-JAX formulation: materialized scores, jnp softmax, probs
            # saved by autodiff for backward — no framework ops
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / float(d) ** 0.5
            mask = jnp.tril(jnp.ones((s, s), bool))
            scores = jnp.where(mask, scores.astype(jnp.float32), -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
            if c.dropout > 0 and key is not None:
                probs = _dropout(probs, c.dropout, jax.random.fold_in(key, 0))
            ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        else:
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k)
            probs = scaled_upper_triang_masked_softmax(
                scores.reshape(b * h, s, s), 1.0 / float(d) ** 0.5
            ).reshape(b, h, s, s)
            if c.dropout > 0 and key is not None:
                probs = _dropout(probs, c.dropout, jax.random.fold_in(key, 0))
            ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        # Output projection contracted directly over (heads, d) — no
        # transpose back to (b, s, h*d) (RowParallelLinear.headwise).
        return self.attn_out.headwise(p["attn_out"], ctx)

    def _attention_tp_overlap(self, p, x, drop, seed):
        """The flash attention block with the TP boundary collectives fused
        into ring collective matmuls (``ops.collective_matmul``): the
        packed QKV projection rides the ag→matmul ring (SP) or the plain
        local GEMM with an overlapped-psum backward (copy_matmul), and the
        output projection the matmul→reduce-scatter / matmul→all-reduce
        ring — no blocking all-gather of the activation anywhere in the
        block, forward or backward. The weight packing is the same
        (q-heads | k-heads | v-heads) feature order every other path uses,
        so ``shard_params_for_tp`` shards are shared with the blocking
        oracle."""
        c = self.config
        h, hkv, d = c.local_heads, c.local_kv_heads, c.head_dim
        from apex_tpu.amp.lists import apply_op_rules
        from apex_tpu.ops import collective_matmul as cm
        xc, w_qkv, b_qkv, w_out = apply_op_rules(
            "attention", x, p["qkv"]["weight"], p["qkv"].get("bias"),
            p["attn_out"]["weight"])
        proj = cm.all_gather_matmul if self.sp else cm.copy_matmul
        y = proj(xc, w_qkv, axis_name=self.axis, seq_dim=1)
        if b_qkv is not None:
            y = y + b_qkv
        b_sz, s_len = y.shape[0], y.shape[1]
        q = y[..., :h * d].reshape(b_sz, s_len, h, d)
        k = y[..., h * d:(h + hkv) * d].reshape(b_sz, s_len, hkv, d)
        v = y[..., (h + hkv) * d:].reshape(b_sz, s_len, hkv, d)
        ctx = flash_attention(q, k, v, causal=True, layout="bshd",
                              dropout_rate=drop, dropout_seed=seed)
        epi = cm.matmul_reduce_scatter if self.sp else cm.matmul_all_reduce
        out = epi(ctx.reshape(b_sz, s_len, h * d), w_out,
                  axis_name=self.axis, seq_dim=1)
        if "bias" in p["attn_out"]:
            out = out + p["attn_out"]["bias"]
        return out

    def _mlp(self, p, x):
        if self.moe:
            from apex_tpu.transformer.moe import moe_layer
            c = self.config
            if self.sp:
                # Megatron-SP boundary: the residual stream is seq-sharded
                # over tp; routing needs every rank to see identical full
                # sequences (the expert ffn shards split the SAME tokens'
                # GEMMs), so gather on entry and re-scatter on exit — the
                # same all-gather/reduce-scatter placement the dense MLP's
                # Col/Row linears use, hoisted around the whole MoE block.
                x = self._sp_gather(x)
            y, aux = moe_layer(
                p["moe"], x, k=c.moe_top_k,
                capacity_factor=c.moe_capacity_factor,
                axis_name=c.ep_axis, tp_axis=self.axis, priority="gate")
            if self.sp:
                y = self._sp_scatter(y)
            return y, aux
        h = self.mlp_up(p["mlp_up"], x)
        h = jax.nn.gelu(h, approximate=True)
        if self.config.remat and self.config.remat_policy == "save_attn_mlp":
            from jax.ad_checkpoint import checkpoint_name

            h = checkpoint_name(h, "mlp_h")
        return self.mlp_down(p["mlp_down"], h)

    def _sp_scatter(self, x):
        """Enter the SP region: this tp rank's seq slice. Backward
        all-gathers the cotangent so upstream (embedding, pos) parameters
        see every position's contribution (Megatron's
        ``_ScatterToSequenceParallelRegion``)."""
        return _sp_scatter_seq1(x, self.axis)

    def _sp_gather(self, x):
        """Leave the SP region: full sequence. Backward takes this rank's
        slice of the (replicated) cotangent — the plain all_gather transpose
        (psum_scatter) would multiply by tp_size."""
        return _sp_gather_seq1(x, self.axis)

    def sp_grad_sync(self, grads):
        """All-reduce over tp the gradients of parameters applied to
        seq-sharded activations (block LNs and row-linear biases) — each tp
        rank only saw its sequence slice's contribution. The analog of
        Megatron's sequence-parallel param-grad all-reduce hook. No-op when
        SP is off."""
        if not self.sp:
            return grads
        lay = dict(grads["layers"])
        for name in ("ln1_w", "ln1_b", "ln2_w", "ln2_b"):
            lay[name] = jax.lax.psum(lay[name], self.axis)
        # moe layers have no mlp_down; their expert-bank grads come from
        # FULL (gathered) sequences so need no tp sync (see _mlp)
        for mod in ("attn_out", "mlp_down"):
            if mod not in lay:
                continue
            m = dict(lay[mod])
            if "bias" in m:
                m["bias"] = jax.lax.psum(m["bias"], self.axis)
            lay[mod] = m
        out = dict(grads)
        out["layers"] = lay
        return out

    def _block(self, p, x, key):
        """Residual block. Dense: → new x. MoE: → (new x, router aux)."""
        c = self.config
        with monitor_spans.span("gpt/attn"):
            a = self._attention(p, fused_layer_norm(x, p["ln1_w"], p["ln1_b"]), key)
        if c.remat and c.remat_policy in ("save_attn", "save_attn_mlp"):
            from jax.ad_checkpoint import checkpoint_name

            a = checkpoint_name(a, "attn_out")
        if c.dropout > 0 and key is not None:
            a = _dropout(a, c.dropout, jax.random.fold_in(key, 1))
        x = x + a

        def mlp_half(p_, x_):
            return self._mlp(p_, fused_layer_norm(x_, p_["ln2_w"], p_["ln2_b"]))

        if c.remat and c.remat_policy == "mlp_only":
            mlp_half = jax.checkpoint(mlp_half)
        with monitor_spans.span("gpt/mlp"):
            m = mlp_half(p, x)
        aux = None
        if self.moe:
            m, aux = m
        if c.dropout > 0 and key is not None:
            m = _dropout(m, c.dropout, jax.random.fold_in(key, 2))
        x = x + m
        return (x, aux) if self.moe else x

    def wrapped_block(self):
        """The transformer block with the config's remat policy applied —
        the unit both :meth:`hidden_states` and the pipeline stage
        partitioner (``pipeline_parallel/build_model.py``) iterate."""
        c = self.config
        block = self._block
        if c.remat:
            if c.remat_policy == "save_attn":
                block = jax.checkpoint(
                    block,
                    policy=jax.checkpoint_policies.save_only_these_names(
                        "attn_out"),
                )
            elif c.remat_policy == "save_attn_mlp":
                block = jax.checkpoint(
                    block,
                    policy=jax.checkpoint_policies.save_only_these_names(
                        "attn_out", "mlp_h"),
                )
            elif c.remat_policy == "mlp_only":
                pass  # _block already wraps its mlp half in jax.checkpoint
            else:
                block = jax.checkpoint(block)
        return block

    # --- KV-cached inference branch -------------------------------------------
    #
    # The decode-time twin of _attention/_block: prefill runs the training
    # forward once over the prompt and EXPOSES each layer's k/v in the
    # attention-native cache layout (b, h_kv, s, d); decode_block runs ONE
    # token through a block against the pre-allocated cache via the fused
    # decode-attention op. Cache allocation, the in-place
    # dynamic_update_slice writes, and sampling live in
    # apex_tpu.inference.DecodeEngine — this branch holds only model math,
    # so a weight-layout change cannot strand the inference path.
    # Inference-only: no dropout, single-chip (tp_size == 1), dense MLP.

    def check_decode_supported(self):
        c = self.config
        if c.tp_size > 1 or self.moe or c.cp_axis is not None:
            raise NotImplementedError(
                "the KV-cached decode path is single-chip dense-MLP only "
                "(tp_size == 1, no MoE, no context parallelism) — serve "
                "tp-sharded checkpoints by merging shards first")

    def _proj_qkv_bshd(self, p, x):
        """(b, s, H) → seq-major q (b, s, h, d), k/v (b, s, h_kv, d) via
        the packed projection — the SAME weight slicing every training
        path uses (``bshd_qkv_projection``), so cached k/v are the
        training forward's k/v activations by construction."""
        from apex_tpu.ops.attention import bshd_qkv_projection
        c = self.config
        return bshd_qkv_projection(
            x, p["qkv"]["weight"], p["qkv"].get("bias"),
            c.local_heads, c.local_kv_heads, c.head_dim)

    def _proj_attn_out(self, p, ctx):
        """(b, s, h, d) context → (b, s, H) through the output weight."""
        from apex_tpu.ops.attention import bshd_output_projection
        c = self.config
        y = bshd_output_projection(
            ctx, p["attn_out"]["weight"], c.local_heads, c.head_dim)
        if "bias" in p["attn_out"]:
            y = y + p["attn_out"]["bias"]
        return y

    def prefill_block(self, p, x):
        """One block of the PREFILL forward: the training block (pre-LN →
        causal attention → residual → pre-LN → MLP → residual, no dropout)
        that additionally returns this layer's (k, v) in the cache layout
        (b, h_kv, s, d) — what the engine writes into cache positions
        [0, s)."""
        h_in = fused_layer_norm(x, p["ln1_w"], p["ln1_b"])
        q, k, v = self._proj_qkv_bshd(p, h_in)
        from apex_tpu.ops.attention import flash_attention
        ctx = flash_attention(q, k, v, causal=True, layout="bshd")
        x = x + self._proj_attn_out(p, ctx)
        m = self._mlp(p, fused_layer_norm(x, p["ln2_w"], p["ln2_b"]))
        return x + m, (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))

    def decode_qkv(self, p, x):
        """ONE token's attention inputs: pre-LN + packed projection of the
        residual stream x (b, 1, H) → q (b, h, d) plus this token's cache
        rows k/v (b, h_kv, 1, d) — shaped for the engine's
        ``dynamic_update_slice`` write at the current position (the write
        happens BEFORE attention so the token attends to itself)."""
        h_in = fused_layer_norm(x, p["ln1_w"], p["ln1_b"])
        q, k, v = self._proj_qkv_bshd(p, h_in)
        return q[:, 0], k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)

    def decode_block(self, p, x, q, k_lay, v_lay, lengths, rel_bias=None,
                     block_tables=None, kv_scales=None):
        """One token through one block against this layer's cache slices
        (ALREADY holding the token's own k/v row — the engine writes
        between :meth:`decode_qkv` and this call): x (b, 1, H) is the
        block's residual-stream input, ``q`` (b, h, d) the token's query
        heads, ``k_lay``/``v_lay`` (b, h_kv, max_s, d), ``lengths`` (b,)
        the live prefix length INCLUDING this token. ``rel_bias``: an
        optional causal BucketedBias the engine threads from the model's
        ``decode_rel_bias`` hook (T5-style relative bias at decode —
        recomputed in-kernel from the tiny table). ``block_tables``: the
        serving engine's paged-cache path — ``k_lay``/``v_lay`` are then
        the shared (num_blocks, h_kv, block_size, d) pool and the table
        maps each slot's logical kv blocks to pool blocks (see
        :func:`apex_tpu.ops.decode_attention`). ``kv_scales``: the int8
        paged pool's ``(k_scale, v_scale)`` per-row dequantization
        factors (the serving engine's ``kv_dtype="int8"`` knob).
        Returns the block output (b, 1, H)."""
        from apex_tpu.ops import decode_attention
        k_scale, v_scale = kv_scales if kv_scales is not None else (None,
                                                                    None)
        ctx = decode_attention(q, k_lay, v_lay, lengths, bias=rel_bias,
                               block_tables=block_tables,
                               k_scale=k_scale, v_scale=v_scale)
        x = x + self._proj_attn_out(p, ctx[:, None])
        m = self._mlp(p, fused_layer_norm(x, p["ln2_w"], p["ln2_b"]))
        return x + m

    # --- forward --------------------------------------------------------------

    def hidden_states(self, params, tokens, key=None):
        x, _ = self.hidden_states_with_aux(params, tokens, key)
        return x

    def hidden_states_with_aux(self, params, tokens, key=None):
        """(final hidden states, MoE router aux dict or None). The aux
        scalars (load_balance_loss, router_z_loss, drop_fraction) are
        per-layer means."""
        c = self.config
        s = tokens.shape[1]
        with monitor_spans.span("gpt/embed"):
            x = self.embedding(params["embedding"], tokens)
            if c.cp_axis is not None:
                # tokens are a sequence shard: gather the shard's GLOBAL
                # positions (zigzag stripes under ring)
                x = x + params["pos_embedding"][self._cp_positions(s)]
            else:
                x = x + params["pos_embedding"][:s]
        if c.cp_axis is not None and key is not None:
            # decorrelate the residual-dropout streams per cp rank:
            # each shard holds DIFFERENT global token positions, so an
            # unfolded key would hand them identical local-coordinate
            # keep masks (ADVICE r4). GPTPipeline folds its data-like
            # axes (incl. cp) before its stage fns — which bypass this
            # method — so the fold lives here for the direct path only.
            key = jax.random.fold_in(
                key, jax.lax.axis_index(c.cp_axis))
        if self.sp:
            x = self._sp_scatter(x)  # residual stream is seq-sharded

        block = self.wrapped_block()
        if self.moe:
            from apex_tpu.transformer.moe import router_aux_zeros
            aux0 = router_aux_zeros()
        else:
            aux0 = None

        if c.scan_layers:
            def body(carry, layer_and_key):
                x, aux = carry
                layer, i = layer_and_key
                k = None if key is None else jax.random.fold_in(key, i)
                out = block(layer, x, k)
                if self.moe:
                    x, a = out
                    aux = jax.tree.map(lambda t, u: t + u, aux, a)
                else:
                    x = out
                return (x, aux), None

            (x, aux), _ = jax.lax.scan(
                body, (x, aux0), (params["layers"], jnp.arange(c.num_layers))
            )
        else:
            # unrolled: larger program (compile time ~ num_layers) but no
            # while-loop carry copies / dynamic-slices; XLA schedules across
            # layer boundaries
            aux = aux0
            for i in range(c.num_layers):
                layer = jax.tree.map(lambda a, i=i: a[i], params["layers"])
                k = None if key is None else jax.random.fold_in(key, i)
                out = block(layer, x, k)
                if self.moe:
                    x, a = out
                    aux = jax.tree.map(lambda t, u: t + u, aux, a)
                else:
                    x = out
        if self.moe:
            aux = jax.tree.map(lambda t: t / c.num_layers, aux)
        if self.sp:
            x = self._sp_gather(x)  # full seq for the head
        return fused_layer_norm(x, params["lnf_w"], params["lnf_b"]), aux

    def logits(self, params, tokens, key=None):
        """Tied unembedding: local shard logits (b, s, V/tp)."""
        x = self.hidden_states(params, tokens, key)
        return self.unembed(params, x)

    def unembed(self, params, x):
        """Hidden states → local-shard logits. Under tp the input passes
        through copy-to-region (identity forward, psum backward) — the LM
        head is column-parallel over vocab, so each shard's matmul backward
        yields only its vocab slice's contribution to dx; without the psum
        transpose, per-rank gradients of everything upstream (final LN, the
        whole stack) would be partial sums (Megatron's
        ``parallel_lm_logits`` places the same ``copy_to`` for the same
        reason)."""
        if self.axis is not None:
            x = tp_lib.copy_to_tensor_model_parallel_region(x, self.axis)
        return jnp.dot(x, params["embedding"]["weight"].T)

    def loss_fn(self, params, tokens, targets, key=None, loss_mask=None,
                return_aux=False):
        """Mean LM loss via vocab-parallel CE (the reference's
        ``vocab_parallel_cross_entropy`` on the last stage). ``loss_mask``
        (tokens-shaped, 1 = count) weights the mean — the consumer of
        ``get_ltor_masks_and_position_ids``'s loss mask (reference
        ``pipeline_parallel/utils.py:303``: EOD and padding positions are
        excluded from the loss there the same way).

        With MoE, the router's load-balance and z losses enter with the
        config coefficients; ``return_aux=True`` additionally returns the
        aux dict (per-layer-mean load_balance_loss / router_z_loss /
        drop_fraction — the drop stat training loops should log)."""
        x, aux = self.hidden_states_with_aux(params, tokens, key)
        with monitor_spans.span("gpt/unembed_xent"):
            logits = self.unembed(params, x)
            losses = tp_lib.vocab_parallel_cross_entropy(
                logits, targets, axis_name=self.axis
            )
            loss = tp_lib.masked_mean(losses, loss_mask)
        if self.moe:
            c = self.config
            loss = (loss + c.moe_aux_coeff * aux["load_balance_loss"]
                    + c.moe_z_coeff * aux["router_z_loss"])
        return (loss, aux) if return_aux else loss


def _dropout(x, rate, key):
    """Counter-hash dropout — the same PRNG family as the in-kernel
    attention masks (``ops.pallas.attention.dropout_keep``): one scalar
    threefry draw for the seed, then ~10 integer ops per element vs the
    per-element threefry of ``jax.random.bernoulli`` (measured ~50 → ~3 ms
    of residual-dropout cost per flagship train step, PERF.md r4)."""
    from apex_tpu.ops.pallas.attention import dropout_keep
    seed = seed_from_key(key)
    # (rows, cols) coordinates rather than one flat arange: a flat int32
    # counter overflows at 2^31 elements (review r4) — splitting on the
    # last axis keeps both coordinates small at any realistic shape
    n = x.shape[-1]
    rows = jnp.arange(x.size // n, dtype=jnp.int32)[:, None]
    cols = jnp.arange(n, dtype=jnp.int32)[None, :]
    keep = dropout_keep(seed, jnp.int32(0), rows, cols, rate
                        ).reshape(x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


def shard_params_for_tp(params, tp: int, config: GPTConfig):
    """Split a replicated (tp=1) :meth:`GPTModel.init` pytree into per-rank
    TP shards: every leaf gains a leading ``(tp,)`` axis holding rank r's
    slice at index r (replicated leaves are broadcast). Shard under
    ``P('tp', ...)`` specs and index ``[0]`` inside ``shard_map``.

    The layout mirrors the layers' own partitioning (reference
    ``tensor_parallel/layers.py``): qkv/mlp_up column-sharded by head /
    output features, attn_out/mlp_down row-sharded by input features,
    embedding vocab-sharded; LNs, positions and row-linear biases
    replicated. The qkv split respects the (q-heads | k-heads | v-heads)
    grouped feature packing of :meth:`GPTModel._attention`, including
    narrower k/v groups under grouped-query attention."""
    c = config
    hq, hkv = c.num_heads, c.kv_heads
    d = c.head_dim

    def split_qkv(x, feature_axis):
        # features packed (q: hq*d | k: hkv*d | v: hkv*d); each rank takes
        # its head range from every group
        q, k, v = jnp.split(
            x, [hq * d, (hq + hkv) * d], axis=feature_axis)

        def per_rank(y, heads):
            shape = y.shape
            hs = y.reshape(
                *shape[:feature_axis], heads, d, *shape[feature_axis + 1:])
            return [
                jnp.take(hs, jnp.arange(i * heads // tp, (i + 1) * heads // tp),
                         axis=feature_axis).reshape(
                             *shape[:feature_axis], heads // tp * d,
                             *shape[feature_axis + 1:])
                for i in range(tp)
            ]

        qs, ks, vs = per_rank(q, hq), per_rank(k, hkv), per_rank(v, hkv)
        return jnp.stack([
            jnp.concatenate([qs[i], ks[i], vs[i]], axis=feature_axis)
            for i in range(tp)
        ])

    def shard_layer_leaf(path, x):
        name = "/".join(str(p) for p in path)
        # leaves carry a leading (num_layers,) axis from the stacked init
        if "qkv" in name:  # weight (L, F, hid) and bias (L, F) split alike
            return split_qkv(x, 1)
        if "mlp_up" in name:  # weight (L, ffn, hid) or bias (L, ffn)
            return jnp.stack(jnp.split(x, tp, axis=1))
        if "attn_out" in name and "weight" in name:  # (L, hid, hid) row-shard
            return split_qkv_like_rows(x)
        if "mlp_down" in name and "weight" in name:  # (L, hid, ffn)
            return jnp.stack(jnp.split(x, tp, axis=2))
        if "moe" in name:
            # expert banks shard each expert's ffn dim (MoEMLP tp layout):
            # w1 (L, E, hid, ffn) col-, w2 (L, E, ffn, hid) row-, b1
            # (L, E, ffn) alike; router (L, hid, E) and b2 (L, E, hid)
            # replicate
            if "w1" in name:
                return jnp.stack(jnp.split(x, tp, axis=3))
            if "b1" in name or "w2" in name:
                return jnp.stack(jnp.split(x, tp, axis=2))
        return jnp.broadcast_to(x, (tp,) + x.shape)

    def split_qkv_like_rows(x):
        # attn_out input features are (heads, d) contiguous — row-shard by
        # head range
        L, out = x.shape[0], x.shape[1]
        y = x.reshape(L, out, hq, d)
        per = hq // tp
        return jnp.stack([
            y[:, :, i * per:(i + 1) * per].reshape(L, out, per * d)
            for i in range(tp)
        ])

    return {
        "embedding": {
            "weight": jnp.stack(
                jnp.split(params["embedding"]["weight"], tp, axis=0)),
        },
        "pos_embedding": jnp.broadcast_to(
            params["pos_embedding"], (tp,) + params["pos_embedding"].shape),
        "layers": jax.tree_util.tree_map_with_path(
            shard_layer_leaf, params["layers"]),
        "lnf_w": jnp.broadcast_to(params["lnf_w"], (tp,) + params["lnf_w"].shape),
        "lnf_b": jnp.broadcast_to(params["lnf_b"], (tp,) + params["lnf_b"].shape),
    }


# --- sequence-parallel boundary collectives (custom transposes) ---------------

import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _sp_scatter_seq1(x, axis_name):
    size = jax.lax.axis_size(axis_name)
    if x.shape[1] % size:
        # a flooring chunk would silently DROP the trailing tokens from
        # every rank's shard (and the backward gather would rebuild the
        # wrong length deep inside XLA) — fail at trace time, naming the
        # knob
        raise ValueError(
            f"GPTConfig(sequence_parallel=True): sequence length "
            f"{x.shape[1]} is not divisible by the {axis_name!r} axis "
            f"size {size} — the SP residual stream shards the sequence "
            f"per tp rank; pad the sequence to a multiple of {size}")
    rank = jax.lax.axis_index(axis_name)
    chunk = x.shape[1] // size
    return jax.lax.dynamic_slice_in_dim(x, rank * chunk, chunk, axis=1)


def _sp_scatter_fwd(x, axis_name):
    return _sp_scatter_seq1(x, axis_name), None


def _sp_scatter_bwd(axis_name, _, g):
    return (jax.lax.all_gather(g, axis_name, axis=1, tiled=True),)


_sp_scatter_seq1.defvjp(_sp_scatter_fwd, _sp_scatter_bwd)


@_functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _sp_gather_seq1(x, axis_name):
    return jax.lax.all_gather(x, axis_name, axis=1, tiled=True)


def _sp_gather_fwd(x, axis_name):
    return _sp_gather_seq1(x, axis_name), None


def _sp_gather_bwd(axis_name, _, g):
    size = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    chunk = g.shape[1] // size
    return (jax.lax.dynamic_slice_in_dim(g, rank * chunk, chunk, axis=1),)


_sp_gather_seq1.defvjp(_sp_gather_fwd, _sp_gather_bwd)
