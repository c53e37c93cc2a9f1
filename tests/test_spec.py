"""Speculative decoding subsystem (ISSUE 15): the fused
verify-and-accept tail, the drafter framework, engine integration at
batch 1 and under scheduler churn, the rewind contract, int8 KV
quantization, and the spec record/gate plumbing.

The load-bearing witnesses:

* greedy spec output TOKEN-IDENTICAL to the non-speculative baseline
  for BOTH drafters, batch 1 and under churn, with every jitted body's
  cache size pinned at 1 across spec rounds;
* the fused verify kernel == the XLA fallback token-for-token on
  shared noise (greedy and rejection-sampling modes);
* a scripted worst-case all-rejected round under churn restores block
  tables/lengths/free-list exactly and the resumed stream equals the
  non-speculative stream;
* int8-KV decode logit error bounded against the float parity oracle
  (which stays the default pool);
* eager knob-naming validation (vocab/kv_dtype/batch/bounds) — never a
  deep XLA shape error;
* the CLOSED ``spec`` schema's drift tests (nan-in-OK fails, junk keys
  fail, reason-less SKIP fails).
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import jax.random as jr
import numpy as np
import pytest

from apex_tpu import monitor
from apex_tpu.inference import DecodeEngine
from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.ops import fused_verify
from apex_tpu.serving import Request, ServeTelemetry, ServingEngine
from apex_tpu.spec import Drafter, ModelDrafter, NGramDrafter, validate_drafter

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import validate_metrics  # noqa: E402

_CFG = dict(vocab_size=256, max_seq_len=256, hidden_size=64,
            num_layers=2, num_heads=4, tp_size=1, remat=False,
            attention_impl="flash")


def _model(seed=0, **over):
    cfg = GPTConfig(**{**_CFG, **over})
    model = GPTModel(cfg)
    return model, model.init(jr.PRNGKey(seed))


def _requests(n=6, seed=0, vocab=256, prompt_rng=(4, 40), newtok=(2, 10)):
    rng = np.random.default_rng(seed)
    return [Request(
        rid=i,
        prompt=rng.integers(0, vocab, int(rng.integers(*prompt_rng))
                            ).astype(np.int32),
        max_new_tokens=int(rng.integers(*newtok)))
        for i in range(n)]


class WrongDrafter(Drafter):
    """Adversarial scripted drafter: proposes the BASELINE stream's
    next token + 1 (mod V) at every position — guaranteed first-row
    rejection under greedy verification, so every round is the
    worst case (accept_len == 0, one corrected token emitted)."""

    def __init__(self, k, baseline_by_len, vocab):
        self.k = int(k)
        self._by_len = baseline_by_len  # context len -> true next token
        self._vocab = int(vocab)

    def propose(self, stream, context):
        nxt = self._by_len.get((stream, len(context)), 0)
        return np.full((self.k,), (nxt + 1) % self._vocab, np.int32)


# --- the fused verify op ------------------------------------------------------

class TestFusedVerify:
    def _logits(self, b=3, K=4, V=256, seed=0):
        return jax.random.normal(jr.PRNGKey(seed), (b, K + 1, V))

    def test_greedy_accept_semantics(self):
        logits = self._logits()
        cand = np.asarray(jnp.argmax(logits, -1))
        V = logits.shape[-1]
        drafted = np.zeros((3, 4), np.int32)
        drafted[0] = [cand[0, 0], cand[0, 1], (cand[0, 2] + 1) % V,
                      cand[0, 3]]
        drafted[1] = [(cand[1, 0] + 1) % V] * 4
        drafted[2] = cand[2, :4]
        a, nxt = fused_verify(logits, jnp.asarray(drafted))
        assert list(np.asarray(a)) == [2, 0, 4]
        # the corrected token is row a's candidate — a match with what
        # the non-speculative greedy loop would have produced
        assert list(np.asarray(nxt)) == [cand[0, 2], cand[1, 0],
                                         cand[2, 4]]

    def test_kernel_matches_fallback_greedy(self):
        logits = self._logits(b=5, K=3)
        drafted = jnp.asarray(
            np.asarray(jnp.argmax(logits, -1))[:, :3])  # mostly accept
        a1, t1 = fused_verify(logits, drafted, impl="xla")
        a2, t2 = fused_verify(logits, drafted, impl="pallas")
        assert (np.asarray(a1) == np.asarray(a2)).all()
        assert (np.asarray(t1) == np.asarray(t2)).all()

    @pytest.mark.parametrize("K", [8, 32])
    def test_kernel_handles_long_drafts(self, K):
        """The drafted-id/noise operands ride (k+1, 1) columns — every k
        validate_drafter allows must run the kernel path, not crash at
        the old 8-lane carrier width (review finding): K=8 is the first
        width that broke, K=32 the MAX_DRAFT_K ceiling."""
        logits = self._logits(b=2, K=K, seed=K)
        drafted = jnp.asarray(np.asarray(jnp.argmax(logits, -1))[:, :K])
        a1, t1 = fused_verify(logits, drafted, impl="xla")
        a2, t2 = fused_verify(logits, drafted, impl="pallas")
        assert (np.asarray(a1) == np.asarray(a2)).all()
        assert (np.asarray(t1) == np.asarray(t2)).all()
        key = jr.PRNGKey(1)
        a3, t3 = fused_verify(logits, drafted, key, temperature=0.9,
                              top_k=11, impl="xla")
        a4, t4 = fused_verify(logits, drafted, key, temperature=0.9,
                              top_k=11, impl="pallas")
        assert (np.asarray(a3) == np.asarray(a4)).all()
        assert (np.asarray(t3) == np.asarray(t4)).all()

    @pytest.mark.parametrize("top_k,top_p", [(0, 1.0), (17, 1.0),
                                             (0, 0.9), (13, 0.85)])
    def test_kernel_matches_fallback_sampled(self, top_k, top_p):
        """Shared-noise discipline: temperature/top-k/top-p rejection
        sampling agrees token-for-token across impls (the fused_sample
        parity anchor, extended to the verify tail)."""
        logits = self._logits(b=4, K=4, seed=3)
        drafted = jnp.asarray(np.asarray(jnp.argmax(logits, -1))[:, :4])
        key = jr.PRNGKey(11)
        a1, t1 = fused_verify(logits, drafted, key, temperature=0.7,
                              top_k=top_k, top_p=top_p, impl="xla")
        a2, t2 = fused_verify(logits, drafted, key, temperature=0.7,
                              top_k=top_k, top_p=top_p, impl="pallas")
        assert (np.asarray(a1) == np.asarray(a2)).all()
        assert (np.asarray(t1) == np.asarray(t2)).all()

    def test_sampled_acceptance_is_exact_for_sure_things(self):
        """A drafted token carrying ~all filtered probability mass is
        always accepted; one the filter removed is always rejected."""
        V = 128
        logits = np.full((1, 3, V), -20.0, np.float32)
        logits[0, :, 7] = 20.0  # a near-point-mass target distribution
        drafted = np.array([[7, 3]], np.int32)  # d0 sure, d1 filtered-out
        a, nxt = fused_verify(jnp.asarray(logits), jnp.asarray(drafted),
                              jr.PRNGKey(0), temperature=1.0, top_k=1)
        assert int(np.asarray(a)[0]) == 1  # d0 accepted, d1 rejected
        # the residual excludes the rejected draft; with top_k=1 only
        # token 7 survives the filter, and 7 != 3 keeps it drawable
        assert int(np.asarray(nxt)[0]) == 7

    def test_validation_names_the_contract(self):
        logits = self._logits()
        with pytest.raises(ValueError, match=r"\(b, k\+1, V\)"):
            fused_verify(logits[0], jnp.zeros((3, 4), jnp.int32))
        with pytest.raises(ValueError, match="drafted must be"):
            fused_verify(logits, jnp.zeros((3, 2), jnp.int32))
        with pytest.raises(ValueError, match="requires a PRNG key"):
            fused_verify(logits, jnp.zeros((3, 4), jnp.int32),
                         temperature=0.5)
        with pytest.raises(ValueError, match="fused_sample"):
            fused_verify(logits[:, :1], jnp.zeros((3, 0), jnp.int32))


# --- drafters -----------------------------------------------------------------

class TestDrafters:
    def test_ngram_proposes_static_k_and_learns_repeats(self):
        d = NGramDrafter(k=4, n=2)
        ctx = [1, 2, 3, 1, 2, 3, 1, 2]
        out = d.propose(0, ctx)
        assert out.shape == (4,) and out.dtype == np.int32
        # the order-2 table maps (1, 2) -> 3, (2, 3) -> 1, (3, 1) -> 2
        assert list(out) == [3, 1, 2, 3]
        d.release(0)
        assert 0 not in d._streams

    def test_ngram_incremental_state_survives_context_growth(self):
        d = NGramDrafter(k=2, n=2)
        d.propose(7, [1, 2, 3])
        table, consumed = d._streams[7]
        assert consumed == 3
        d.propose(7, [1, 2, 3, 4, 5])
        table2, consumed2 = d._streams[7]
        assert consumed2 == 5 and table2 is table  # incremental, not rebuilt
        # a SHRUNK context (reused stream id) resets instead of aliasing
        d.propose(7, [9, 9])
        assert d._streams[7][1] == 2

    def test_model_drafter_single_compile_across_streams(self):
        dm, dp = _model(seed=5, num_layers=1, hidden_size=32, num_heads=2)
        d = ModelDrafter(dm, dp, k=3)
        for stream in range(3):
            out = d.propose(stream, [1, 2, 3, 4, 5 + stream])
            assert out.shape == (3,)
        assert d.engine.decode_step._cache_size() == 1
        d.release(1)
        assert 1 not in d._streams and 0 in d._streams

    def test_validate_drafter_names_every_knob(self):
        model, _ = _model()
        dm, dp = _model(seed=1, vocab_size=128)
        with pytest.raises(ValueError, match="vocab_size"):
            validate_drafter(ModelDrafter(dm, dp, k=2), model.config,
                             needed_rows=8)
        with pytest.raises(ValueError, match=r"draft\.k"):
            validate_drafter(NGramDrafter.__new__(NGramDrafter),
                             model.config, needed_rows=8)
        with pytest.raises(ValueError, match="block_size"):
            dm2, dp2 = _model(seed=2)
            validate_drafter(ModelDrafter(dm2, dp2, k=2, block_size=64),
                             model.config, needed_rows=8, block_size=16)
        with pytest.raises(ValueError, match="max_seq_len"):
            dm3, dp3 = _model(seed=3, max_seq_len=128)
            validate_drafter(ModelDrafter(dm3, dp3, k=2), model.config,
                             needed_rows=10_000)
        with pytest.raises(ValueError, match=r"k must be"):
            NGramDrafter(k=0)


# --- DecodeEngine speculation -------------------------------------------------

class TestDecodeEngineSpec:
    def test_greedy_parity_both_drafters(self):
        model, params = _model()
        eng = DecodeEngine(model)
        prompt = jr.randint(jr.PRNGKey(1), (1, 24), 0, 256)
        base = np.asarray(eng.generate(params, prompt, 20))
        out = np.asarray(eng.generate(params, prompt, 20,
                                      draft=NGramDrafter(k=4)))
        assert (out == base).all()
        dm, dp = _model(seed=3, num_layers=1, hidden_size=32, num_heads=2)
        md = ModelDrafter(dm, dp, k=4)  # same static k: one executable
        out2 = np.asarray(eng.generate(params, prompt, 20, draft=md))
        assert (out2 == base).all()
        # one executable for EVERY jitted body across spec rounds
        assert eng.spec_verify_step._cache_size() == 1
        assert eng.decode_step._cache_size() == 1
        assert md.engine.decode_step._cache_size() == 1

    def test_self_drafter_accepts_everything(self):
        """The exactness sanity: drafting with the TARGET model itself
        must accept every draft (the verifier reproduces the drafter's
        own greedy choices)."""
        model, params = _model()
        eng = DecodeEngine(model)
        prompt = jr.randint(jr.PRNGKey(2), (1, 16), 0, 256)
        base = np.asarray(eng.generate(params, prompt, 12))
        out = np.asarray(eng.generate(params, prompt, 12,
                                      draft=ModelDrafter(model, params,
                                                         k=3)))
        assert (out == base).all()
        assert eng.last_spec_stats.acceptance_rate == 1.0

    def test_all_rejected_drafter_still_exact(self):
        """The scripted worst case at batch 1: every round rejects at
        row 0 and emits exactly the corrected (baseline) token."""
        model, params = _model()
        eng = DecodeEngine(model)
        prompt = jr.randint(jr.PRNGKey(3), (1, 16), 0, 256)
        T = 10
        base = np.asarray(eng.generate(params, prompt, T))
        by_len = {(0, 16 + i): int(base[0, i]) for i in range(T)}
        out = np.asarray(eng.generate(params, prompt, T,
                                      draft=WrongDrafter(3, by_len, 256)))
        assert (out == base).all()
        st = eng.last_spec_stats
        assert st.accepted == 0 and st.rounds == T - 1

    def test_sampled_spec_generates_within_bounds(self):
        """temperature>0 spec runs the rejection-sampling tail; the
        output is a valid token stream of the right shape (exact
        distributional parity is the op-level test's job)."""
        model, params = _model()
        eng = DecodeEngine(model, temperature=0.8, top_k=20)
        prompt = jr.randint(jr.PRNGKey(4), (1, 16), 0, 256)
        out = np.asarray(eng.generate(params, prompt, 8,
                                      key=jr.PRNGKey(9),
                                      draft=NGramDrafter(k=3)))
        assert out.shape == (1, 8)
        assert ((out >= 0) & (out < 256)).all()

    def test_eager_validation(self):
        model, params = _model()
        eng = DecodeEngine(model)
        prompt2 = jr.randint(jr.PRNGKey(5), (2, 16), 0, 256)
        with pytest.raises(ValueError, match="batch 1"):
            eng.generate(params, prompt2, 4, draft=NGramDrafter(k=2))
        prompt = prompt2[:1]
        dm, dp = _model(seed=6, vocab_size=128)
        with pytest.raises(ValueError, match="vocab_size"):
            eng.generate(params, prompt, 4,
                         draft=ModelDrafter(dm, dp, k=2))
        with pytest.raises(ValueError, match=r"draft\.k"):
            eng.generate(params, prompt, 4,
                         draft=WrongDrafter.__new__(WrongDrafter))
        # 16 + 238 fits the cache for PLAIN decode, but the k=4 draft
        # rows push past it: the SPEC bound must fire, naming draft.k
        with pytest.raises(ValueError, match=r"draft\.k \(4\)"):
            eng.generate(params, prompt, 238, draft=NGramDrafter(k=4))


# --- ServingEngine speculation under churn ------------------------------------

class TestServingSpec:
    def _serve_pair(self, draft_factory, *, num_blocks=None, n=6,
                    kv_dtype=None):
        model, params = _model()
        mk = lambda: ServingEngine(  # noqa: E731
            model, num_slots=3, block_size=16, prefill_chunk=16,
            num_blocks=num_blocks, kv_dtype=kv_dtype)
        base_eng = mk()
        base = base_eng.serve(params, _requests(n), telemetry=False)
        spec_eng = mk()
        out = spec_eng.serve(params, _requests(n), telemetry=False,
                             draft=draft_factory())
        return base, out, spec_eng

    def test_churn_parity_ngram(self):
        base, out, eng = self._serve_pair(lambda: NGramDrafter(k=3))
        want = {r.rid: list(r.tokens) for r in base}
        assert all(list(r.tokens) == want[r.rid] for r in out)
        assert eng.last_stats.spec_rounds > 0
        assert eng.prefill_chunk._cache_size() == 1
        assert eng.spec_step._cache_size() == 1
        assert eng.decode_step._cache_size() <= 1  # may never dispatch

    def test_churn_parity_model_drafter(self):
        dm, dp = _model(seed=7, num_layers=1, hidden_size=32, num_heads=2)
        base, out, eng = self._serve_pair(
            lambda: ModelDrafter(dm, dp, k=3))
        want = {r.rid: list(r.tokens) for r in base}
        assert all(list(r.tokens) == want[r.rid] for r in out)
        assert eng.spec_step._cache_size() == 1

    def test_churn_parity_under_pool_pressure(self):
        """An undersized pool forces preemption DURING spec rounds —
        evict/readmit, drafter streams surviving eviction, block
        rewind — and the streams must still match the (equally
        pressured) non-speculative baseline."""
        base, out, eng = self._serve_pair(lambda: NGramDrafter(k=3),
                                          num_blocks=13, n=8)
        want = {r.rid: list(r.tokens) for r in base}
        assert all(list(r.tokens) == want[r.rid] for r in out)
        assert eng.spec_step._cache_size() == 1
        assert eng.prefill_chunk._cache_size() == 1

    def test_spec_telemetry_events_and_acceptance(self, tmp_path):
        """Spec rounds emit schema-valid ``spec``-phase lifecycle
        events and the serve-record fields carry the acceptance
        rollup."""
        model, params = _model()
        eng = ServingEngine(model, num_slots=2, block_size=16,
                            prefill_chunk=16)
        path = tmp_path / "events.jsonl"
        monitor.enable(str(path))
        try:
            tel = ServeTelemetry(slots=2, window_s=0, status="SKIP",
                                 reason="cpu test")
            eng.serve(params, _requests(2), telemetry=tel,
                      draft=NGramDrafter(k=3))
        finally:
            monitor.disable()
        lines = [json.loads(ln) for ln in path.read_text().splitlines()]
        spec_events = [r for r in lines if r.get("phase") == "spec"]
        assert spec_events, "no spec lifecycle events emitted"
        for r in spec_events:
            assert monitor.validate(r) == []
            assert 0 <= r["accepted_len"] <= r["draft_k"] == 3
        fields = tel.final_fields(None, None)
        # one lifecycle record per slot-round, mirrored in the rollup
        # (spec_slot_rounds: slot×dispatch — the engine's
        # last_stats.spec_rounds counts dispatches)
        assert fields["spec_slot_rounds"] == len(spec_events)
        assert fields["spec_drafted"] == 3 * len(spec_events)
        assert 0.0 <= fields["spec_acceptance_rate"] <= 1.0
        assert fields["draft_k"] == 3

    def test_int8_spec_matches_int8_plain(self):
        """Speculation composes with the quantized pool: int8+spec is
        token-identical to int8 without spec (the parity oracle for
        the composition)."""
        base, out, eng = self._serve_pair(lambda: NGramDrafter(k=3),
                                          kv_dtype="int8")
        want = {r.rid: list(r.tokens) for r in base}
        assert all(list(r.tokens) == want[r.rid] for r in out)


class TestRewindContract:
    def test_all_rejected_round_restores_pool_state(self):
        """The satellite's scripted worst case: drive ONE spec round
        whose drafts are all rejected and assert block tables, lengths,
        and the allocator free list are exactly what a plain decode
        step would have left — then that the resumed stream is
        token-identical to non-speculative decode."""
        model, params = _model()
        # baseline stream for the adversarial drafter and the final
        # check; a 14-token prompt makes the k=3 reservation CROSS a
        # block boundary, so the rewind really frees a block
        ref_eng = ServingEngine(model, num_slots=2, block_size=16,
                                prefill_chunk=16)
        req = _requests(1, prompt_rng=(14, 15), newtok=(8, 9))
        base = ref_eng.serve(params, _requests(
            1, prompt_rng=(14, 15), newtok=(8, 9)), telemetry=False)
        base_tokens = list(base[0].tokens)
        rid = base[0].rid
        plen = len(base[0].prompt)
        by_len = {(rid, plen + i): t for i, t in enumerate(base_tokens)}

        eng = ServingEngine(model, num_slots=2, block_size=16,
                            prefill_chunk=16)
        sched = eng.make_scheduler()
        K = 3
        draft = WrongDrafter(K, by_len, 256)
        pool = eng.init_pool()
        key = jr.PRNGKey(0)
        r = req[0]
        sched.submit(r)
        sched.admit(0.0)
        while True:
            w = sched.next_prefill(0.0)
            if w is None:
                break
            pool, tok, _ = eng.prefill_chunk(
                params, pool, jnp.asarray(sched.tables.row(w.slot)),
                jnp.asarray(w.tokens), jnp.int32(w.start),
                jnp.int32(w.live), key)
            sched.note_prefill(w, int(tok), 0.0)
        (slot,) = sched.decoding_slots()
        # snapshot BEFORE the round
        free_before = list(sched.allocator._free)
        table_before = sched.tables.asarray().copy()
        len_before = sched.slot_length(slot)
        # one all-rejected spec round
        toks, lens = sched.decode_batch(0.0, lookahead=K)
        drafted = np.zeros((2, K), np.int32)
        drafted[slot] = draft.propose(rid, sched.slot_context(slot))
        tok_mat = np.zeros((2, K + 1), np.int32)
        tok_mat[:, 0] = toks
        tok_mat[:, 1:] = drafted
        pool, acc, nxt = eng.spec_step(
            params, pool, jnp.asarray(sched.tables.asarray()),
            jnp.asarray(tok_mat), jnp.asarray(lens),
            jnp.asarray(drafted), key)
        acc, nxt = np.asarray(acc), np.asarray(nxt)
        assert int(acc[slot]) == 0  # the scripted worst case engaged
        sched.note_spec(drafted, acc, nxt, 0.0)
        # the round emitted exactly the baseline's next token
        assert list(r.tokens)[-1] == base_tokens[len(r.tokens) - 1]
        # lengths advanced by exactly one (the corrected token's row)
        assert sched.slot_length(slot) == len_before + 1
        # block tables: entries past the frontier rewound to dead block,
        # entries at/below it untouched
        import apex_tpu.serving.kv_blocks as kvb
        keep = kvb.blocks_needed(sched.slot_length(slot), 16)
        table_now = sched.tables.asarray()
        assert (table_now[slot, :keep] == table_before[slot, :keep]).all()
        assert (table_now[slot, keep:] == kvb.DEAD_BLOCK).all()
        # free list EXACTLY restored minus the (possibly zero) blocks a
        # plain decode step would also have claimed for the new row
        claimed = keep - kvb.blocks_needed(len_before, 16)
        assert sched.allocator._free == free_before[:len(free_before)
                                                    - claimed]
        sched.allocator.check_accounting()
        # drive the stream to completion WITHOUT speculation: the
        # resumed stream must be the non-speculative stream
        while True:
            batch = sched.decode_batch(0.0)
            if batch is None:
                break
            toks, lens = batch
            pool, sampled, _ = eng.decode_step(
                params, pool, jnp.asarray(sched.tables.asarray()),
                jnp.asarray(toks), jnp.asarray(lens), key)
            sched.note_decode(np.asarray(sampled), 0.0)
        assert list(r.tokens) == base_tokens
        assert eng.spec_step._cache_size() == 1


# --- int8 KV quantization -----------------------------------------------------

class TestQuantizedKV:
    def test_logit_error_bounded_vs_float_oracle(self):
        """Teacher-forced decode logits through the int8 pool stay
        within a small bound of the float pool's — the parity oracle
        the record's kv_quant_logit_err field reports."""
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
        import bench
        model, params = _model()
        prompt = np.asarray(jr.randint(jr.PRNGKey(1), (32,), 0, 256),
                            np.int32)
        err, q_mb, o_mb = bench._spec_quant_err(
            model, params, prompt, 8, slots=1, block=16, chunk=16,
            cast=None)
        assert err < 0.05, f"int8 KV logit error {err} out of bound"
        assert q_mb < o_mb  # the pool really shrank

    def test_pool_layout_and_bytes(self):
        model, params = _model()
        q = ServingEngine(model, num_slots=2, block_size=16,
                          kv_dtype="int8")
        f = ServingEngine(model, num_slots=2, block_size=16)
        pool = q.init_pool()
        assert pool["k"].dtype == jnp.int8
        assert pool["k_scale"].shape == (2, q.num_blocks, 16)
        # int8 + fp32 scales still well under half the fp32 oracle
        assert q.pool_bytes() < f.pool_bytes() / 2
        # the float pool stays the default (the parity oracle)
        assert "k_scale" not in f.init_pool()

    def test_quantized_serve_stream_is_reasonable(self):
        """The int8 engine serves end to end; its streams may differ
        from the oracle's token-for-token (quantization is lossy) but
        lengths and accounting must hold."""
        model, params = _model()
        eng = ServingEngine(model, num_slots=2, block_size=16,
                            prefill_chunk=16, kv_dtype="int8")
        done = eng.serve(params, _requests(4), telemetry=False)
        assert len(done) == 4
        assert all(len(r.tokens) == r.max_new_tokens for r in done)
        assert eng.decode_step._cache_size() == 1
        assert eng.prefill_chunk._cache_size() == 1

    def test_eager_kv_dtype_validation(self):
        model, params = _model()
        with pytest.raises(ValueError, match="kv_dtype"):
            ServingEngine(model, num_slots=2, block_size=16,
                          kv_dtype="fp8")
        # a model with a decode relative bias cannot ride the int8 path
        model.decode_rel_bias = lambda p: None
        with pytest.raises(ValueError, match="relative-position bias"):
            ServingEngine(model, num_slots=2, block_size=16,
                          kv_dtype="int8")

    def test_rel_bias_models_cannot_speculate(self):
        """The spec verify bodies do not thread the bucketed decode
        bias, so both draft= paths must refuse a decode_rel_bias model
        eagerly (review finding: a silent accept/reject against
        unbiased spec logits would break the parity contract)."""
        model, params = _model()
        model.decode_rel_bias = lambda p: None
        eng = DecodeEngine(model)
        prompt = jr.randint(jr.PRNGKey(1), (1, 8), 0, 256)
        with pytest.raises(ValueError, match="relative-position bias"):
            eng.generate(params, prompt, 4, draft=NGramDrafter(k=2))
        srv = ServingEngine(model, num_slots=2, block_size=16)
        with pytest.raises(ValueError, match="relative-position bias"):
            srv.serve(params, _requests(1), telemetry=False,
                      draft=NGramDrafter(k=2))

    def test_decode_attention_scale_contract(self):
        from apex_tpu.ops import decode_attention
        q = jnp.zeros((1, 4, 64))
        pool8 = jnp.zeros((4, 2, 128, 64), jnp.int8)
        poolf = jnp.zeros((4, 2, 128, 64))
        tables = jnp.zeros((1, 2), jnp.int32)
        lengths = jnp.ones((1,), jnp.int32)
        sc = jnp.ones((4, 128))
        with pytest.raises(ValueError, match="PAGED path only"):
            decode_attention(q, pool8, pool8, lengths)
        with pytest.raises(ValueError, match="BOTH k_scale and v_scale"):
            decode_attention(q, pool8, pool8, lengths,
                             block_tables=tables, k_scale=sc)
        with pytest.raises(ValueError, match="BOTH k_scale and v_scale"):
            decode_attention(q, poolf, poolf, lengths,
                             block_tables=tables, k_scale=sc, v_scale=sc)
        with pytest.raises(ValueError, match="per-row scales"):
            decode_attention(q, pool8, pool8, lengths,
                             block_tables=tables,
                             k_scale=jnp.ones((4, 64)), v_scale=sc)


# --- the spec record / schema drift -------------------------------------------

class TestSpecRecord:
    def _ok_fields(self):
        return dict(tokens_per_s_request=100.0, acceptance_rate=0.8,
                    draft_k=4, drafter="ngram", greedy_parity=True,
                    jit_cache_ok=True, backend="cpu")

    def test_ok_record_validates(self):
        rec = monitor.MetricsRegistry().emit_spec("OK", **self._ok_fields())
        assert monitor.validate(rec) == []

    def test_nan_in_ok_fails(self):
        with pytest.raises(ValueError, match="non-finite"):
            monitor.MetricsRegistry().emit_spec(
                "OK", tokens_per_s_request=float("nan"))
        # and an externally-produced nan record fails the validator too
        rec = monitor.MetricsRegistry().emit_spec("OK",
                                                  **self._ok_fields())
        rec["acceptance_rate"] = float("nan")
        assert any("non-finite" in e for e in monitor.validate(rec))

    def test_junk_key_fails_closed_schema(self):
        rec = monitor.MetricsRegistry().emit_spec("OK", **self._ok_fields())
        rec["junk_key"] = 1
        assert any("unexpected key" in e for e in monitor.validate(rec))

    def test_reasonless_skip_fails(self):
        with pytest.raises(ValueError, match="reason"):
            monitor.MetricsRegistry().emit_spec("SKIP")
        rec = monitor.MetricsRegistry().emit_spec("SKIP", reason="x")
        del rec["reason"]
        assert any("reason" in e for e in monitor.validate(rec))

    def test_validator_cli_forced_and_content_dispatch(self, tmp_path):
        rec = monitor.MetricsRegistry().emit_spec("OK", **self._ok_fields())
        good = tmp_path / "spec.json"
        good.write_text(json.dumps(rec))
        assert validate_metrics.main(["--spec", str(good)]) == 0
        # content dispatch: no flag needed, kind routes the schema
        assert validate_metrics.main([str(good)]) == 0
        # a file that lost its kind fails AS a spec artifact
        bad = tmp_path / "lost.json"
        stripped = {k: v for k, v in rec.items() if k != "kind"}
        bad.write_text(json.dumps(stripped))
        assert validate_metrics.main(["--spec", str(bad)]) == 1
        # junk keys fail through the CLI too
        rec2 = dict(rec, junk=1)
        junk = tmp_path / "junk.json"
        junk.write_text(json.dumps(rec2))
        assert validate_metrics.main(["--spec", str(junk)]) == 1

    def test_report_renders_spec_line(self):
        rec = monitor.MetricsRegistry().emit_spec(
            "OK", **{**self._ok_fields(), "speedup": 1.5,
                     "kv_quant_logit_err": 0.01})
        summary = monitor.aggregate([rec])
        assert summary["spec"]["speedup"] == 1.5
        from apex_tpu.monitor.report import render
        text = render(summary)
        assert "spec" in text and "1.50x vs non-spec" in text
        assert "accept 80%" in text
