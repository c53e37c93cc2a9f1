"""Drafter framework for speculative decoding: propose a STATIC ``k``
tokens per round, cheaply, per stream.

A drafter's contract is deliberately host-facing and tiny — the device
side of speculation (batched verification, the fused accept/reject
tail, cache rewind) lives entirely in the engines; a drafter only has
to GUESS. Wrong guesses cost one wasted verify row, never correctness:
the fused verifier (:func:`apex_tpu.ops.fused_verify`) accepts exactly
the prefix the target model would have produced, so the emitted stream
is token-identical to non-speculative decoding regardless of drafter
quality. What the drafter controls is the ACCEPTANCE RATE, i.e. how
many of the k drafted tokens survive per round — the amortization
factor on the target's weight/KV streaming.

Two implementations:

* :class:`NGramDrafter` — host-side n-gram lookahead: an order-``n``
  suffix table built incrementally from each stream's own context
  (prompt + generated tokens) predicts the continuation; misses repeat
  the last token. Zero device memory, zero extra compiled programs —
  the cheapest possible drafter, strong on self-similar text (code,
  chat templates, the repetitive tails greedy LMs produce).
* :class:`ModelDrafter` — a small :class:`~apex_tpu.models.gpt.
  GPTConfig` model with its own KV cache per stream, driven through
  ONE jitted single-token step (the target engine's own decode-step
  program shape: batch-1, stable avals, compiled exactly once across
  every stream, round, and churn event). Context rows are teacher-
  forced through the same step — no per-prompt-length prefill program
  exists, so the zero-recompile discipline holds by construction.

Streams: engines key drafter state by request id. State survives
preemption for free — an evicted-and-recomputed request's context
re-grows token-identically, so the incremental ``consumed`` frontier
stays valid; a context that SHRANK (a genuinely new stream reusing an
id) resets the stream. :meth:`Drafter.release` frees a finished
stream's state (the drafter's memory is bounded by concurrent streams,
never by request history).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from apex_tpu.monitor import spans as monitor_spans

__all__ = ["Drafter", "NGramDrafter", "ModelDrafter", "validate_drafter"]

#: sane bound on the per-round draft length: past ~32 the verify step's
#: k+1-row cost dominates any plausible acceptance run
MAX_DRAFT_K = 32


class Drafter:
    """The drafter protocol: ``propose(stream, context)`` returns
    exactly ``self.k`` int32 token ids continuing ``context`` (the
    stream's full prompt + generated tokens so far). ``k`` is STATIC
    for the drafter's lifetime — it shapes the engines' compiled verify
    programs. ``vocab_size`` is the id space the proposals live in
    (``None`` = inherits the target's, e.g. the n-gram drafter which
    only ever replays context tokens)."""

    k: int = 0
    vocab_size: Optional[int] = None
    #: paged-pool granularity the drafter's cache rides, when it has
    #: one; None = the drafter imposes no block constraint
    block_size: Optional[int] = None

    def propose(self, stream: int,
                context: Sequence[int]) -> np.ndarray:  # pragma: no cover
        raise NotImplementedError

    def release(self, stream: int) -> None:
        """Drop per-stream state (request finished); default no-op."""

    def reset(self) -> None:
        """Drop ALL stream state (a fresh serve run reusing ids)."""


def validate_drafter(draft: Drafter, config, *, needed_rows: int,
                     cache_rows: Optional[int] = None,
                     block_size: Optional[int] = None) -> int:
    """Eager construction-time validation of a drafter against a target
    engine — every mismatch raises a knob-naming ``ValueError`` here,
    never a deep XLA shape error three layers down. Returns ``draft.k``.

    ``needed_rows`` is the worst-case cache rows a spec round can touch
    (prompt + new tokens + k); ``cache_rows`` the drafter's own cache
    capacity when it has one; ``block_size`` the target engine's paged
    granularity (checked against a paged drafter's).
    """
    k = getattr(draft, "k", None)
    if not isinstance(k, int) or not 1 <= k <= MAX_DRAFT_K:
        raise ValueError(
            f"draft.k must be an int in [1, {MAX_DRAFT_K}] (it shapes "
            f"the compiled verify program); got {k!r}")
    dv = getattr(draft, "vocab_size", None)
    if dv is not None and dv != config.vocab_size:
        raise ValueError(
            f"drafter vocab_size ({dv}) != target vocab_size "
            f"({config.vocab_size}) — drafted ids would index a "
            f"different token space; use a drafter model sharing the "
            f"target's tokenizer/vocab")
    db = getattr(draft, "block_size", None)
    if block_size is not None and db is not None and db != block_size:
        raise ValueError(
            f"drafter block_size ({db}) != engine block_size "
            f"({block_size}) — the drafter's paged cache cannot ride "
            f"the engine's block tables; construct the drafter with "
            f"block_size={block_size} (or leave it None)")
    rows = getattr(draft, "cache_rows", None) \
        if cache_rows is None else cache_rows
    if rows is not None and rows < needed_rows:
        raise ValueError(
            f"drafter cache holds {rows} rows but a spec round can "
            f"touch {needed_rows} (prompt + max_new_tokens + k) — "
            f"raise the drafter's max_seq_len to >= {needed_rows}")
    depth = getattr(draft, "depth", None)
    branching = getattr(draft, "branching", None)
    if depth is not None and branching is not None:
        depth, branching = int(depth), int(branching)
        nodes = depth * branching
        if nodes > MAX_DRAFT_K:
            raise ValueError(
                f"draft tree ({branching} branches x depth {depth} = "
                f"{nodes} nodes) exceeds MAX_DRAFT_K={MAX_DRAFT_K} "
                f"verify rows — shrink branching or depth so "
                f"branching x depth <= {MAX_DRAFT_K}")
        if depth + 1 > needed_rows:
            raise ValueError(
                f"draft tree depth ({depth}) + 1 bonus row exceeds the "
                f"per-slot row cap ({needed_rows}) — even an empty slot "
                f"cannot hold one tree round's writes; shrink the "
                f"drafter's depth to <= {needed_rows - 1} or raise the "
                f"engine's max_seq_len (rows round up to whole "
                f"block_size blocks, so the cap is "
                f"ceil(max_seq_len / block_size) x block_size)")
        if not isinstance(getattr(draft, "chain_k", k), int) \
                or not 1 <= getattr(draft, "chain_k", k) <= depth:
            raise ValueError(
                f"tree drafter chain_k must be an int in [1, depth="
                f"{depth}] (the chain-fallback rung cannot draft deeper "
                f"than the tree); got {getattr(draft, 'chain_k', k)!r}")
    return k


class NGramDrafter(Drafter):
    """Host-side n-gram/lookahead drafter: no device memory, no extra
    compiled programs.

    Per stream, an order-``n`` suffix table maps each length-``n``
    window of the context to the token that followed it (latest
    occurrence wins — recency beats frequency on the self-similar text
    speculation pays off on). :meth:`propose` walks the table ``k``
    steps from the context's tail, falling back to repeating the last
    token on a miss (the cheapest guess that is often right for
    degenerate/greedy tails). The table updates INCREMENTALLY from the
    stream's ``consumed`` frontier, so a propose costs O(new tokens +
    k) dict work.
    """

    def __init__(self, k: int = 4, n: int = 3):
        if not 1 <= int(k) <= MAX_DRAFT_K:
            raise ValueError(
                f"NGramDrafter k must be in [1, {MAX_DRAFT_K}], got {k}")
        if int(n) < 1:
            raise ValueError(f"NGramDrafter n must be >= 1, got {n}")
        self.k = int(k)
        self.n = int(n)
        # stream -> (suffix table, consumed context length)
        self._streams: Dict[int, Any] = {}

    def propose(self, stream: int, context: Sequence[int]) -> np.ndarray:
        n = self.n
        table, consumed = self._streams.get(stream, (None, 0))
        if table is None or consumed > len(context):
            table, consumed = {}, 0  # fresh (or shrunk: a reused id)
        ctx = [int(t) for t in context]
        for i in range(max(consumed, n), len(ctx)):
            table[tuple(ctx[i - n:i])] = ctx[i]
        self._streams[stream] = (table, len(ctx))
        window: List[int] = ctx[-n:] if len(ctx) >= n else ctx[:]
        out = []
        for _ in range(self.k):
            guess = table.get(tuple(window[-n:]), window[-1])
            out.append(guess)
            window.append(guess)
        return np.asarray(out, np.int32)

    def release(self, stream: int) -> None:
        self._streams.pop(stream, None)

    def reset(self) -> None:
        self._streams.clear()


class ModelDrafter(Drafter):
    """A small-``GPTConfig`` model drafter: greedy continuations from a
    cheap model, one KV cache per stream.

    The drafter rides ONE jitted single-token decode step (the
    :class:`~apex_tpu.inference.engine.DecodeEngine` program at
    batch 1): context tokens are teacher-forced through it row by row
    and the k proposals greedy-decoded from the frontier — stable avals
    throughout, so the step compiles exactly once no matter how many
    streams, rounds, or churn events it serves (witnessed by
    ``decode_step._cache_size() == 1`` in the spec tests). Drafted
    rows land in the cache past the trusted frontier and are simply
    re-written when the real stream catches up — the contiguous-cache
    analog of the serving engine's block-table rewind (length masking
    IS the rewind).

    ``max_seq_len`` sizes every stream's cache (128-multiple, the
    decode kernel's tiling rule) and must cover the target's worst
    case plus ``k`` draft rows; the engines validate that eagerly via
    :func:`validate_drafter`. Vocab must equal the target's — checked
    at wiring time, never discovered as an XLA gather error.
    """

    def __init__(self, model, params, *, k: int = 4,
                 max_seq_len: Optional[int] = None,
                 block_size: Optional[int] = None):
        from apex_tpu.inference.engine import DecodeEngine

        if not 1 <= int(k) <= MAX_DRAFT_K:
            raise ValueError(
                f"ModelDrafter k must be in [1, {MAX_DRAFT_K}], got {k}")
        self.k = int(k)
        self.model = model
        self.params = params
        self.vocab_size = int(model.config.vocab_size)
        self.block_size = None if block_size is None else int(block_size)
        if max_seq_len is None:
            # default the cache to the model's position table rounded UP
            # to the decode kernel's 128-row tiling grid (the slack holds
            # no positions; generation stays capped by the table)
            max_seq_len = ((model.config.max_seq_len + 127) // 128) * 128
        # greedy proposals: the point-mass drafts the exact-acceptance
        # math in ops.fused_verify assumes
        self.engine = DecodeEngine(model, max_seq_len=max_seq_len,
                                   temperature=0.0)
        self.cache_rows = self.engine.max_s
        # stream -> {"cache": donated-cache tree, "consumed": rows
        # trusted as real context}
        self._streams: Dict[int, Dict[str, Any]] = {}
        self._key = None  # lazily built greedy dummy key (fixed avals)

    def _step(self, cache, tok: int, pos: int):
        import jax
        import jax.numpy as jnp

        if self._key is None:
            self._key = jax.random.PRNGKey(0)  # apexlint: disable=APX502
        return self.engine.decode_step(
            self.params, cache, jnp.asarray([tok], jnp.int32),
            jnp.int32(pos), self._key)

    def propose(self, stream: int, context: Sequence[int]) -> np.ndarray:
        st = self._streams.get(stream)
        if st is None or st["consumed"] > len(context):
            st = {"cache": self.engine.init_cache(1), "consumed": 0}
        cache, consumed = st["cache"], st["consumed"]
        ctx = [int(t) for t in context]
        if len(ctx) - 1 + self.k > self.cache_rows:
            raise ValueError(
                f"ModelDrafter cache ({self.cache_rows} rows) cannot "
                f"hold context ({len(ctx)}) + k ({self.k}) draft rows — "
                f"raise max_seq_len (the engines validate this bound at "
                f"wiring time; hitting it here means the drafter was "
                f"driven directly past it)")
        # one spec_draft span per round: its trace slice (and the
        # decode_step device scopes nested under it) joins the round's
        # spec lifecycle record through the ambient serve trace id —
        # with monitoring off, a profiler annotation and no record
        with monitor_spans.span("spec_draft", stream=int(stream)):
            # teacher-force the unconsumed context rows (every token but
            # the last writes its k/v; its sampled candidate is discarded)
            for i in range(consumed, len(ctx) - 1):
                cache, _, _ = self._step(cache, ctx[i], i)
            # draft greedily from the frontier; each step writes the fed
            # token's k/v one row further (rows past the trusted
            # frontier: re-written by the next teacher-forcing pass if
            # rejected)
            out = []
            tok = ctx[-1]
            for j in range(self.k):
                cache, nxt, _ = self._step(cache, tok, len(ctx) - 1 + j)
                tok = int(np.asarray(nxt)[0])
                out.append(tok)
        st["cache"], st["consumed"] = cache, len(ctx)
        self._streams[stream] = st
        return np.asarray(out, np.int32)

    def release(self, stream: int) -> None:
        self._streams.pop(stream, None)

    def reset(self) -> None:
        self._streams.clear()
