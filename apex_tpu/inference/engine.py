"""KV-cached autoregressive decode engine for the flagship GPT.

The serving path the training repo lacked: generating a token by re-running
the full forward over the whole prefix is O(s²) work per token; with a KV
cache each new token costs one token's projections plus ONE streaming pass
over the cache — the O(s) HBM-bound floor decode lives at ("LLM Inference
Acceleration via Efficient Operation Fusion", arXiv:2502.17728: the decode
hot path is memory-bound and won by removing staging traffic and per-token
dispatch, not FLOPs).

Design contract (what makes ``decode_step`` compile ONCE and stay compiled):

* **Pre-allocated, donated cache.** ``init_cache`` allocates
  ``(layers, batch, kv_heads, max_s, head_dim)`` k/v buffers up front —
  the attention-native layout :func:`apex_tpu.ops.decode_attention` reads
  directly. Every step updates them via ``lax.dynamic_update_slice`` at a
  *traced* position, so the avals never change; ``donate_argnums`` hands
  the buffers back to XLA so the update is in place — no per-token HBM
  realloc, no copy of the O(layers·batch·max_s) state.
* **Stable avals everywhere.** The step signature is
  ``(params, cache, tokens (b,), pos scalar, key)`` — every argument keeps
  one shape/dtype for the whole generation, so the jit cache holds exactly
  one executable (asserted by ``tests/test_inference.py`` via
  ``decode_step._cache_size()``).
* **Static sampling config.** temperature/top-k are fixed at engine
  construction (they select the sampling program, not data).

Prefill reuses the training forward (flash-attention blocks) over the whole
prompt at once and returns the populated cache — one compile per distinct
prompt length (pad prompts to a few bucket lengths to bound that).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.inference.sampling import sample_logits
from apex_tpu.models.gpt import GPTModel
from apex_tpu.monitor import spans as monitor_spans
from apex_tpu.monitor import trace as monitor_trace
from apex_tpu.ops import decode_attention
from apex_tpu.ops.attention import flash_attention
from apex_tpu.parallel import mesh as mesh_lib
from apex_tpu.serving import tp as tp_serving
from apex_tpu.serving.engine import (ModelMath, _pos_rows, cached_attention,
                                     run_layers, winning_path_levels)


@dataclass
class SpecStats:
    """Host-side accounting of one speculative ``generate`` call.

    ``drafted`` counts PATH DEPTH per round (the chain's k; the tree's
    drafted depth), so ``acceptance_rate`` compares across chain and
    tree rounds; ``nodes`` counts total verify rows scored (== drafted
    for chains, branching x depth per tree round) — the denominator of
    draft-compute efficiency."""

    rounds: int = 0
    drafted: int = 0
    accepted: int = 0
    nodes: int = 0

    @property
    def acceptance_rate(self) -> float:
        """Accepted drafts / drafted tokens (0.0 before any round)."""
        return self.accepted / self.drafted if self.drafted else 0.0

    @property
    def efficiency(self) -> float:
        """Emitted tokens (accepted + one bonus per round) per verify
        row scored — what adaptive (k, b) selection maximizes."""
        rows = self.nodes + self.rounds  # + the root row per round
        return (self.accepted + self.rounds) / rows if rows else 0.0


class DecodeEngine:
    """Batched greedy/sampling generation over a :class:`GPTModel`.

    ``engine = DecodeEngine(model)``;
    ``tokens = engine.generate(params, prompt, max_new_tokens)``.

    ``max_seq_len`` sizes the cache (default: the model's) and MUST be a
    multiple of 128 — the fused decode kernel streams the cache in
    128-column tiles, so any other length silently drops to the XLA
    fallback on TPU; that policy-by-accident was worth turning into an
    eager error. A cache may be ROUNDED UP past the model's position
    table (``max_seq_len=((n + 127) // 128) * 128``): the extra rows are
    tiling slack, and ``generate`` still refuses to step positions past
    the table itself. ``cache_dtype`` defaults to the model's param
    dtype; serve bf16 caches for 2x cache capacity at bf16-activation
    quality.
    """

    def __init__(self, model: GPTModel, *, max_seq_len: Optional[int] = None,
                 cache_dtype: Any = None, temperature: float = 0.0,
                 top_k: int = 0, plan=None):
        model.check_decode_supported()
        self.model = model
        c = self.config = model.config
        self.max_s = int(max_seq_len or c.max_seq_len)
        if self.max_s < 1 or self.max_s % 128:
            raise ValueError(
                f"max_seq_len ({self.max_s}) must be a positive multiple "
                f"of 128 (the fused decode kernel's cache-tiling "
                f"constraint) — round the cache up: DecodeEngine(model, "
                f"max_seq_len={((self.max_s + 127) // 128) * 128}); "
                f"generation is still capped by the model's position "
                f"table ({c.max_seq_len})")
        if self.max_s > ((c.max_seq_len + 127) // 128) * 128:
            raise ValueError(
                f"cache max_seq_len ({self.max_s}) exceeds the model's "
                f"position table ({c.max_seq_len}) by more than the "
                f"128-rounding slack")
        self.cache_dtype = cache_dtype or c.dtype
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        # tensor-parallel decode (ROADMAP tier 2c): plan.tp >= 2 shards
        # the cache's kv-head axis and the projections across chips.
        # DecodeEngine is GREEDY-only under tp: its sampled path is
        # jax.random.categorical, whose draws do not compose bitwise
        # across vocab shards (ServingEngine's fused Gumbel tail does)
        self.plan = plan
        self.tp = int(plan.tp) if plan is not None else 1
        self._mesh = None
        # the layer-math seam the four bodies below are written against
        self._math = ModelMath(model, sample_logits,
                               temperature=self.temperature,
                               top_k=self.top_k)
        if self.tp > 1:
            if self.temperature > 0:
                raise ValueError(
                    f"temperature={self.temperature} with plan.tp="
                    f"{self.tp}: DecodeEngine's sampled path draws via "
                    f"jax.random.categorical, which does not compose "
                    f"across vocab shards — decode greedy "
                    f"(temperature=0.0) under tp, or sample through "
                    f"ServingEngine's psum-composed fused tail")
            tp_serving.validate_tp(
                plan, c, engine="DecodeEngine",
                temperature=self.temperature, top_k=self.top_k,
                has_rel_bias=getattr(model, "decode_rel_bias",
                                     None) is not None)
            self._mesh = tp_serving.tp_mesh(self.tp)
            # replicated activations, plain dot + psum (overlap=False):
            # batch and prompt lengths are not tp-divisible in general
            # (the ring contract is witnessed on ServingEngine's programs)
            self._math = tp_serving.ShardedMath(c, overlap=False)
            P = jax.sharding.PartitionSpec
            kv, rep = P(None, None, "tp"), P()
            cache_spec = {"k": kv, "v": kv}
            # the SAME bodies, shard_mapped in place: params arrive as
            # per-rank shards, the cache's kv-head axis is this shard's
            # contiguous slice, the greedy argmax / verify tails
            # psum-compose, logits reassemble the vocab row via output
            # sharding. The tree round stays unmapped: generate()
            # refuses tree drafts under tp
            smap = functools.partial(mesh_lib.shard_map, mesh=self._mesh)
            self._prefill_body = smap(
                self._prefill_body, in_specs=(P("tp"), rep, rep),
                out_specs=(cache_spec, rep, P(None, "tp")))
            self._decode_step_body = smap(
                self._decode_step_body,
                in_specs=(P("tp"), cache_spec, rep, rep, rep),
                out_specs=(cache_spec, rep, P(None, "tp")))
            self._spec_verify_body = smap(
                self._spec_verify_body,
                in_specs=(P("tp"), cache_spec, rep, rep, rep, rep),
                out_specs=(cache_spec, rep, rep))
        # one jitted executable each; decode additionally donates the cache
        # (argnums: params=0, cache=1, tokens=2, pos=3, key=4)
        self.prefill = jax.jit(self._prefill)
        self.decode_step = jax.jit(self._decode_step, donate_argnums=(1,))
        # the speculative round: k+1 tokens scored in one multi-token
        # step + the fused verify tail; avals depend only on the static
        # draft length k, so across rounds it compiles exactly once
        self.spec_verify_step = jax.jit(self._spec_verify_step,
                                        donate_argnums=(1,))
        # the TREE round: N+1 nodes scored in one forward under the
        # tree-attention mask + the fused tree-verify tail; avals depend
        # only on (N+1, depth+1) — both carried by operand SHAPES
        # (parents/anc and the levels iota), so the jit cache holds one
        # executable per (k, b) topology in use and nothing retraces
        # across rounds, streams, or acceptance patterns
        self.spec_tree_step = jax.jit(self._spec_tree_verify_step,
                                      donate_argnums=(1,))
        self.last_spec_stats: Optional[SpecStats] = None

    # --- cache ---------------------------------------------------------------

    def init_cache(self, batch: int):
        """Pre-allocated zeroed KV cache:
        ``{"k"/"v": (layers, batch, kv_heads, max_s, head_dim)}``."""
        c = self.config
        shape = (c.num_layers, batch, c.local_kv_heads, self.max_s,
                 c.head_dim)
        return {"k": jnp.zeros(shape, self.cache_dtype),
                "v": jnp.zeros(shape, self.cache_dtype)}

    def cache_bytes(self, batch: int) -> int:
        """HBM footprint of one cache (both k and v), for capacity math."""
        c = self.config
        itemsize = jnp.dtype(self.cache_dtype).itemsize
        return (2 * c.num_layers * batch * c.local_kv_heads * self.max_s
                * c.head_dim * itemsize)

    def _prepare_params(self, params):
        """tp == 1: passthrough; under tp the per-rank shards, committed
        to the mesh (:func:`apex_tpu.serving.tp.prepare_params`)."""
        return tp_serving.prepare_params(params, self.tp, self.config,
                                         self._mesh)

    def _write_cache(self, cache, i, pos, k, v):
        """``k``/``v`` (b, h_kv, rows, d) into layer ``i`` at rows
        [pos, pos + rows) of the DONATED stacked buffers (layer index
        static, position traced — one executable for all pos)."""
        zero = jnp.int32(0)
        at = (jnp.int32(i), zero, zero, pos, zero)
        return {n: jax.lax.dynamic_update_slice(
            cache[n], x[None].astype(cache[n].dtype), at)
            for n, x in (("k", k), ("v", v))}

    # --- prefill -------------------------------------------------------------

    def _prefill(self, params, tokens, key):
        """Prompt (b, s) → (cache populated at [0, s), next token (b,),
        last-position logits (b, V)). The forward is the training block
        structure (flash attention over the full prompt) with each layer's
        k/v exposed — cache contents ARE the training forward's k/v."""
        with monitor_spans.span("decode_prefill"):
            return self._prefill_body(params, tokens, key)

    def _prefill_body(self, params, tokens, key):
        m, c = self._math, self.config
        params = m.shard(params)
        s = tokens.shape[1]
        x = m.embed(params, tokens)
        x = x + params["pos_embedding"][:s]
        ks, vs = [], []

        def attend(i, q, k, v):
            ks.append(k.transpose(0, 2, 1, 3))  # the cache layout
            vs.append(v.transpose(0, 2, 1, 3))
            return flash_attention(q, k, v, causal=True, layout="bshd")
        x = run_layers(m, params, x, attend)
        logits = m.unembed(params, x[:, -1:])[:, 0]
        # static-length write: s is a trace-time constant of this prompt
        cache = {}
        for n, rows in (("k", ks), ("v", vs)):
            rows = jnp.stack(rows).astype(self.cache_dtype)
            cache[n] = jnp.zeros((*rows.shape[:3], self.max_s, c.head_dim),
                                 self.cache_dtype).at[:, :, :, :s].set(rows)
        return cache, m.sample(logits, key), logits

    # --- decode --------------------------------------------------------------

    def _decode_step(self, params, cache, tokens, pos, key):
        """One generation step: run ``tokens`` (b,) — the tokens at
        position ``pos`` (scalar int32, count of cache rows already live)
        — through the stack against the cache, write their k/v at ``pos``,
        and sample position ``pos+1``'s tokens. Returns (cache, next
        tokens, logits). Avals are independent of ``pos``: compiled
        exactly once per (batch, cache shape)."""
        # trace-time step-anatomy span: every HLO of the decode step
        # carries the decode_step scope into device traces, monitor on or
        # off; entered once per trace, never touching the stable avals
        with monitor_spans.span("decode_step"):
            return self._decode_step_body(params, cache, tokens, pos, key)

    def _decode_step_body(self, params, cache, tokens, pos, key):
        m, c = self._math, self.config
        params = m.shard(params)
        pos = jnp.asarray(pos, jnp.int32)
        x = m.embed(params, tokens[:, None])
        x = x + jax.lax.dynamic_slice(
            params["pos_embedding"], (pos, 0), (1, c.hidden_size))[None]
        lengths = jnp.full((tokens.shape[0],), pos + 1, jnp.int32)
        # T5-style relative bias at decode, for free: a model exposing
        # ``decode_rel_bias(params) -> BucketedBias`` (causal table) gets
        # it threaded into every block's fused decode attention — the
        # kernel recomputes the bias from the tiny table and the live
        # length, so the cache layout, avals, and the zero-recompile
        # contract are untouched. Models without the hook (stock GPT:
        # learned positions) pass None.
        rel_hook = getattr(self.model, "decode_rel_bias", None)
        rel_bias = None if rel_hook is None else rel_hook(params)

        def attend(i, q, k, v):  # (b, 1, heads, d)
            nonlocal cache
            # the row write comes BEFORE attention: the token attends to
            # itself
            cache = self._write_cache(cache, i, pos, k.transpose(0, 2, 1, 3),
                                      v.transpose(0, 2, 1, 3))
            return decode_attention(q[:, 0], cache["k"][i], cache["v"][i],
                                    lengths, bias=rel_bias)[:, None]
        x = run_layers(m, params, x, attend)
        logits = m.unembed(params, x)[:, 0]
        return cache, m.sample(logits, key), logits

    # --- speculative verification --------------------------------------------

    def _spec_verify_step(self, *args):
        """``(params, cache, tokens, pos, drafted, key)`` — one
        speculative round: score ``tokens`` (1, k+1) — the
        pending sampled token followed by the k drafted continuations —
        in ONE multi-token step at cache rows [pos, pos+k], then run the
        fused verify-and-sample tail. Returns ``(cache, accept_len (1,),
        next_token (1,))``. The cache holds all k+1 rows' k/v on return;
        rows past the accepted frontier are rejected-draft garbage that
        the NEXT round's length masking hides and its writes overwrite —
        length masking IS the rewind on a contiguous cache. Avals depend
        only on the static k: one executable across every round."""
        with monitor_spans.span("spec_verify"):
            return self._spec_verify_body(*args)

    def _spec_verify_body(self, params, cache, tokens, pos, drafted, key):
        m = self._math
        params = m.shard(params)
        pos = jnp.asarray(pos, jnp.int32)
        positions = pos + jnp.arange(tokens.shape[1], dtype=jnp.int32)
        x = m.embed(params, tokens)  # (1, K1, H)
        x = x + _pos_rows(params, positions)[None]
        js = jnp.arange(self.max_s, dtype=jnp.int32)
        # prefix-causal per drafted row: row i sees keys [0, pos + i]
        mask = (js[None, None, None, :] <= positions[None, None, :, None])[0]

        def attend(i, q, k, v):  # (1, K1, heads, d)
            nonlocal cache
            # one contiguous K1-row write at the traced frontier (the
            # multi-token sibling of the decode step's single-row write),
            # then K1 queries × the full cache — the prefill-chunk
            # attention at chunk = k+1
            cache = self._write_cache(cache, i, pos, k.transpose(0, 2, 1, 3),
                                      v.transpose(0, 2, 1, 3))
            return cached_attention(q, cache["k"][i][0], cache["v"][i][0],
                                    mask)
        x = run_layers(m, params, x, attend)
        a, nxt = m.verify(m.unembed(params, x), drafted, key)  # (1, K1, V)
        return cache, a, nxt

    def _spec_tree_verify_step(self, *args):
        """``(params, cache, tokens, pos, parents, anc, levels, key)`` —
        one TREE speculative round: score ``tokens`` (1, N+1) — the
        pending token (the root) plus N drafted tree nodes — in ONE
        forward, each node attending the committed cache rows plus its
        own root path via the ``anc`` tree-attention mask, then run the
        fused tree-verify tail and commit the WINNING path's k/v into
        cache rows [pos, pos+accept_len]. Returns ``(cache, accept_len
        (1,), j_star (1,), next_token (1,))``. Unlike the chain step,
        sibling nodes share positions so nothing is cache-scattered
        before the verdict; only the accepted path lands, selected
        level-by-level inside the same program (``levels`` is a
        ``(depth+1,)`` iota whose SHAPE carries the static depth).
        Rows past the accepted frontier hold zeros that next round's
        length masking hides — length masking IS the rewind."""
        with monitor_spans.span("spec_verify"):
            return self._spec_tree_verify_body(*args)

    def _spec_tree_verify_body(self, params, cache, tokens, pos, parents,
                               anc, levels, key):
        m = self._math
        params = m.shard(params)
        pos = jnp.asarray(pos, jnp.int32)
        depth_vec = jnp.sum(anc.astype(jnp.int32), axis=-1) - 1  # (1, N1)
        x = m.embed(params, tokens)  # (1, N1, H)
        # siblings SHARE positions
        x = x + _pos_rows(params, pos + depth_vec[0])[None]
        js = jnp.arange(self.max_s, dtype=jnp.int32)
        # committed rows only: the root's own k/v rides the TREE part
        # (index 0), not the cache, until the verdict commits it
        cache_mask = (js[None, None, :] < pos)[None]  # (1, 1, 1, max_s)
        tree_mask = (anc[0] != 0)[None, None]  # (1, 1, N1 queries, N1 nodes)
        tks, tvs = [], []

        def attend(i, q, k, v):  # (1, N1, heads, d)
            tks.append(k)
            tvs.append(v)
            return cached_attention(q, cache["k"][i][0], cache["v"][i][0],
                                    cache_mask, tree=(k, v, tree_mask))
        x = run_layers(m, params, x, attend)
        a, j_star, nxt = m.verify_tree(m.unembed(params, x), tokens,
                                       parents, anc, key)  # (1, N1, V)
        # commit the winning path: level l of j_star's root path (root =
        # level 0 = the pending token) lands at cache row pos + l; levels
        # past accept_len select nothing and write zeros (masked rows)
        lvl = winning_path_levels(anc, depth_vec, j_star, levels)
        for i, kv in enumerate(zip(tks, tvs)):
            sel_k, sel_v = (jnp.einsum("bln,bnhd->bhld", lvl.astype(t.dtype),
                                       t) for t in kv)
            cache = self._write_cache(cache, i, pos, sel_k, sel_v)
        return cache, a, j_star, nxt

    def _check_speculable(self, b):
        if b != 1:
            raise ValueError(
                f"draft= speculative generation runs batch 1 (accepted "
                f"lengths diverge across rows, and the contiguous cache "
                f"carries one scalar position); got batch {b} — split "
                f"the batch, or serve it through ServingEngine.serve("
                f"draft=...) which speculates per slot")
        if getattr(self.model, "decode_rel_bias", None) is not None:
            # the k+1-row spec scoring does not thread the bucketed
            # relative bias the plain decode step applies — verifying
            # biased baseline logits against unbiased spec logits would
            # silently break the token-identical contract
            raise ValueError(
                "draft= speculative decoding cannot run a model with a "
                "decode relative-position bias (the spec verify step "
                "does not carry the bucketed bias) — generate with "
                "draft=None for this model")

    def _generate_spec_tree(self, params, prompt, max_new_tokens, key,
                            draft, adaptive):
        """The tree-speculative driver behind ``generate(draft=<tree
        drafter>)``: one batched forward scores the whole draft tree,
        the fused tree verify emits the deepest accepted root path + a
        bonus token, and :class:`~apex_tpu.spec.tree.DraftTree` walks
        the verdict back to host tokens. ``adaptive`` (an
        :class:`~apex_tpu.spec.tree.AdaptiveSpecController`) re-picks
        (depth, branching) per round from its static choice set — each
        choice is one pinned executable."""
        from apex_tpu.spec.drafter import validate_drafter
        from apex_tpu.spec.tree import draft_tree

        b, s = prompt.shape
        self._check_speculable(b)
        if self.tp > 1:
            raise ValueError(
                "tree-speculative generation has no tensor-parallel "
                "body — decode tree drafts at tp=1, or use a chain "
                "drafter (which verifies under tp)")
        shapes = (adaptive.choices if adaptive is not None
                  else ((draft.depth, draft.branching),))
        depth_max = max(dd for dd, _ in shapes)
        validate_drafter(draft, self.config,
                         needed_rows=s + max_new_tokens + depth_max)
        if s + max_new_tokens + depth_max - 1 > self.max_s:
            raise ValueError(
                f"prompt ({s}) + max_new_tokens ({max_new_tokens}) + "
                f"tree depth ({depth_max}) - 1 exceeds the cache "
                f"({self.max_s}): a tree round writes depth rows past "
                f"the live frontier — raise max_seq_len or lower the "
                f"drafter's depth")
        if s + max_new_tokens + depth_max - 1 > self.config.max_seq_len:
            raise ValueError(
                f"prompt ({s}) + max_new_tokens ({max_new_tokens}) + "
                f"tree depth ({depth_max}) - 1 steps past the model's "
                f"position table ({self.config.max_seq_len}); drafted "
                f"rows hold real positions too — lower the depth or the "
                f"request")
        cache, tok, _ = self.prefill(params, prompt,
                                     jax.random.fold_in(key, 0))
        stats = SpecStats()
        gen = [int(jnp.asarray(tok)[0])]
        context = [int(t) for t in jnp.asarray(prompt)[0]] + gen
        while len(gen) < max_new_tokens:
            depth, branching = (adaptive.choice(0) if adaptive is not None
                                else (draft.depth, draft.branching))
            tree = draft_tree(branching, depth)
            node_tokens = np.asarray(
                draft.propose_tree(0, context, shape=(depth, branching)),
                np.int32).reshape(-1)
            if node_tokens.shape != (tree.num_nodes,):
                raise ValueError(
                    f"drafter proposed {node_tokens.shape} node tokens; "
                    f"the ({branching}, {depth}) topology needs exactly "
                    f"{tree.num_nodes} (static shapes keep the verify "
                    f"program compiled once per topology)")
            parents, anc = tree.operands(1)
            pos = s + len(gen) - 1
            cache, a, j_star, nxt = self.spec_tree_step(
                params, cache,
                jnp.asarray([[gen[-1], *node_tokens]], jnp.int32),
                jnp.int32(pos), jnp.asarray(parents), jnp.asarray(anc),
                jnp.arange(depth + 1, dtype=jnp.int32),
                jax.random.fold_in(key, 1 + stats.rounds))
            a = int(jnp.asarray(a)[0])
            emitted = tree.path_tokens(node_tokens, a,
                                       int(jnp.asarray(j_star)[0]),
                                       int(jnp.asarray(nxt)[0]))
            gen.extend(emitted)
            context.extend(emitted)
            stats.rounds += 1
            stats.drafted += depth
            stats.accepted += a
            stats.nodes += tree.num_nodes
            if adaptive is not None:
                adaptive.note_round(0, a, depth)
        draft.release(0)
        if adaptive is not None:
            adaptive.release(0)
        self.last_spec_stats = stats
        return jnp.asarray([gen[:max_new_tokens]], jnp.int32)

    def _generate_spec(self, params, prompt, max_new_tokens, key, draft):
        """The speculative driver behind ``generate(draft=...)``."""
        from apex_tpu.spec.drafter import validate_drafter

        b, s = prompt.shape
        self._check_speculable(b)
        K = validate_drafter(draft, self.config,
                             needed_rows=s + max_new_tokens
                             + getattr(draft, "k", 1))
        # the deepest row a round can touch: the last round starts at
        # most at pos = s + max_new - 2 and writes rows pos..pos+K
        if s + max_new_tokens + K - 1 > self.max_s:
            raise ValueError(
                f"prompt ({s}) + max_new_tokens ({max_new_tokens}) + "
                f"draft.k ({K}) - 1 exceeds the cache ({self.max_s}): a "
                f"spec round writes k draft rows past the live frontier "
                f"— raise max_seq_len or lower draft.k")
        if s + max_new_tokens + K - 1 > self.config.max_seq_len:
            raise ValueError(
                f"prompt ({s}) + max_new_tokens ({max_new_tokens}) + "
                f"draft.k ({K}) - 1 steps past the model's position "
                f"table ({self.config.max_seq_len}); drafted rows hold "
                f"real positions too — lower draft.k or the request")
        cache, tok, _ = self.prefill(params, prompt,
                                     jax.random.fold_in(key, 0))
        stats = SpecStats()
        gen = [int(jnp.asarray(tok)[0])]
        context = [int(t) for t in jnp.asarray(prompt)[0]] + gen
        while len(gen) < max_new_tokens:
            drafted = np.asarray(
                draft.propose(0, context), np.int32).reshape(-1)
            if drafted.shape != (K,):
                raise ValueError(
                    f"drafter proposed {drafted.shape} tokens; the "
                    f"contract is exactly k={K} per round (static k "
                    f"keeps the verify program compiled once)")
            pos = s + len(gen) - 1
            cache, a, nxt = self.spec_verify_step(
                params, cache,
                jnp.asarray([[gen[-1], *drafted]], jnp.int32),
                jnp.int32(pos), jnp.asarray(drafted[None]),
                jax.random.fold_in(key, 1 + stats.rounds))
            a = int(jnp.asarray(a)[0])
            emitted = [int(t) for t in drafted[:a]] \
                + [int(jnp.asarray(nxt)[0])]
            gen.extend(emitted)
            context.extend(emitted)
            stats.rounds += 1
            stats.drafted += K
            stats.accepted += a
            stats.nodes += K
        draft.release(0)
        self.last_spec_stats = stats
        return jnp.asarray([gen[:max_new_tokens]], jnp.int32)

    # --- generation loop -----------------------------------------------------

    def generate(self, params, prompt, max_new_tokens: int,
                 key: Optional[jax.Array] = None,
                 draft=None, adaptive=None) -> jax.Array:
        """Greedy/sampled continuation: prompt (b, s) int32 → generated
        tokens (b, max_new_tokens). Python-loop driver over the jit'd
        steps; the loop body re-binds the donated cache each step.

        ``draft`` attaches a :class:`~apex_tpu.spec.drafter.Drafter`
        for speculative decoding (batch 1): each round the drafter
        proposes k tokens, ONE ``spec_verify_step`` scores all k+1
        positions and the fused verify tail accepts the longest valid
        prefix — greedy output token-identical to ``draft=None``, 1 to
        k+1 tokens per target dispatch, acceptance accounted in
        :attr:`last_spec_stats`. A TREE-capable drafter (one exposing
        ``propose_tree`` + ``depth``/``branching``, e.g.
        :class:`~apex_tpu.spec.tree.NGramTreeDrafter`) instead drafts a
        branching tree per round, scored in one forward and verified by
        the fused tree tail — same token-identical contract, 1 to
        depth+1 tokens per dispatch. ``adaptive`` (tree drafters only)
        attaches an :class:`~apex_tpu.spec.tree.AdaptiveSpecController`
        that re-picks (depth, branching) per round from its static
        choice set."""
        b, s = prompt.shape
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens} (the "
                f"prefill itself samples the first token)")
        if s + max_new_tokens > self.max_s:
            raise ValueError(
                f"prompt ({s}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the cache ({self.max_s})")
        # a 128-rounded cache may outsize the position table; positions
        # actually stepped may not (the last DECODED position is
        # s + max_new_tokens - 2: the final sampled token never re-enters)
        if s + max_new_tokens - 1 > self.config.max_seq_len:
            raise ValueError(
                f"prompt ({s}) + max_new_tokens ({max_new_tokens}) steps "
                f"past the model's position table "
                f"({self.config.max_seq_len}); the cache's 128-rounding "
                f"slack holds no positions")
        if self.temperature > 0 and key is None:
            raise ValueError("temperature > 0 generation requires a key")
        if key is None:  # greedy: the key operand is ignored but keeps the
            key = jax.random.PRNGKey(0)  # step signature (and avals) fixed
        # under tp the steps consume the sharded (tp,)-leading tree,
        # committed to the mesh once per generate() call
        params = self._prepare_params(params)
        # one trace id per generate() call: every span the loop emits
        # (decode_prefill, decode_step, spec_verify) joins to this call
        # in a merged timeline. An already-ambient id (a caller's serve/
        # step context) is reused rather than shadowed.
        tid = (monitor_trace.current_trace_id()
               or monitor_trace.new_trace_id("gen"))
        with monitor_trace.trace_context(tid):
            if draft is not None:
                from apex_tpu.spec.tree import is_tree_drafter
                if is_tree_drafter(draft):
                    return self._generate_spec_tree(
                        params, prompt, max_new_tokens, key, draft,
                        adaptive)
                if adaptive is not None:
                    raise ValueError(
                        "adaptive= (k, b) selection needs a tree-capable "
                        "drafter (propose_tree + depth/branching); this "
                        "drafter only proposes chains — use "
                        "NGramTreeDrafter/PagedModelDrafter, or drop "
                        "adaptive=")
                return self._generate_spec(params, prompt,
                                           max_new_tokens, key, draft)
            if adaptive is not None:
                raise ValueError(
                    "adaptive= requires draft= (there is no draft shape "
                    "to adapt without a drafter)")
            cache, tok, _ = self.prefill(params, prompt,
                                         jax.random.fold_in(key, 0))
            out = [tok]
            for t in range(1, max_new_tokens):
                cache, tok, _ = self.decode_step(
                    params, cache, tok, jnp.int32(s + t - 1),
                    jax.random.fold_in(key, t))
                out.append(tok)
            return jnp.stack(out, axis=1)


def jit_encoder(model, *, with_pooler: bool = True):
    """BERT-style encoder serving: the trivial reuse case — encoders have
    no autoregressive structure, so "inference engine" is just the
    training forward jit'd with stable (padded-batch) avals. Returns
    ``encode(params, tokens, token_types=None, pad_mask=None)`` →
    (hidden (b, s, H), pooled (b, H) or None). Pad every request batch to
    fixed (b, s) buckets and pass ``pad_mask`` so one executable serves
    all traffic."""
    @functools.partial(jax.jit, static_argnames=("pool",))
    def _encode(params, tokens, token_types, pad_mask, pool):
        hidden = model.hidden_states(params, tokens, token_types=token_types,
                                     pad_mask=pad_mask)
        pooled = model.pooled(params, hidden) if pool else None
        return hidden, pooled

    def encode(params, tokens, token_types=None, pad_mask=None
               ) -> Tuple[jax.Array, Optional[jax.Array]]:
        return _encode(params, tokens, token_types, pad_mask, with_pooler)

    return encode
