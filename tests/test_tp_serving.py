"""Tensor-parallel serving + disaggregated handoff tests (ISSUE 17).

The contracts under test:

* the eager validation door (:func:`apex_tpu.serving.tp.validate_tp`):
  every divisibility and knob check fails at CONSTRUCTION with the knob
  named — tp over the device count, ``kv_heads % tp``, ``vocab % tp``,
  ``num_slots``/``prefill_chunk`` ring chunking, the GLOBAL
  ``num_blocks`` sizing, the unsupported sampled tails;
* tp greedy parity: the tp∈{2,4} :class:`~apex_tpu.serving.
  ServingEngine` serves the scripted admit/evict/readmit churn schedule
  TOKEN-IDENTICAL to the tp=1 engine, with every jit cache pinned at 1
  and the free list exactly restored — and the same through spec
  rounds, the int8 pool, and a mid-flight weight hot-swap;
* :class:`~apex_tpu.inference.DecodeEngine` under tp: plain and
  speculative greedy generation bitwise vs tp=1;
* the disaggregated prefill→decode handoff (:mod:`apex_tpu.serving.
  disagg`): streamed block digests match the SOURCE pool's rows, the
  decode role's output is token-identical to the monolithic engine,
  corruption/format drift is loud, and the ``handoff`` lifecycle event
  carries ONE trace id across both roles;
* the ``tp_serve`` monitor record: CLOSED schema (junk key fails),
  nan-in-OK fails, reason-less SKIP fails, the ``tools/
  validate_metrics.py --tp-serve`` forced dispatch, the report line,
  and the ``tools/bench_history.py`` throughput + transfer-latency
  series.
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
from apex_tpu.plan.parallel_plan import ParallelPlan, PlanError
from apex_tpu.serving import (
    Request,
    ServeTelemetry,
    ServingEngine,
    export_handoff,
    ingest_handoff,
    prefill_requests,
    read_handoff,
    write_handoff,
)
from apex_tpu.serving.disagg import block_digest
from apex_tpu.serving.tp import validate_tp
from apex_tpu.spec import NGramDrafter

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import bench_history  # noqa: E402
import validate_metrics  # noqa: E402

K = jr.PRNGKey(13)

#: every dimension divisible by the tp values under test (the module
#: fixture in test_serving.py uses vocab 97 — prime on purpose there,
#: useless here)
_CFG = dict(vocab_size=96, max_seq_len=128, hidden_size=32,
            num_layers=2, num_heads=4, num_kv_heads=4,
            attention_impl="flash", remat=False, dropout=0.0)


@pytest.fixture(scope="module")
def tiny_tp():
    model = GPTModel(GPTConfig(**_CFG))
    return model, model.init(K)


def _reqs(n=6, seed=3, max_prompt=30, max_new=12):
    rng = np.random.default_rng(seed)
    return [Request(
        rid=i,
        prompt=np.asarray(
            rng.integers(0, 96, int(rng.integers(1, max_prompt))),
            np.int32),
        max_new_tokens=int(rng.integers(1, max_new)))
        for i in range(n)]


def _engine(model, tp=1, **over):
    kw = dict(num_slots=4, block_size=8, prefill_chunk=16,
              max_seq_len=64, num_blocks=21)
    kw.update(over)
    return ServingEngine(model, plan=ParallelPlan(tp=tp) if tp > 1
                         else None, **kw)


def _toks(done):
    return {r.rid: list(r.tokens) for r in done}


class TestValidateTP:
    """The single eager door: every illegal knob fails at construction
    with the knob NAMED (ParallelPlan.validate message style), never as
    an XLA shape error three dispatches in."""

    def _cfg(self, **over):
        return GPTModel(GPTConfig(**{**_CFG, **over})).config

    def test_non_tensor_axes_rejected(self):
        with pytest.raises(PlanError, match="dp=2 with tp=2"):
            validate_tp(ParallelPlan(dp=2, tp=2), self._cfg(),
                        engine="ServingEngine")

    def test_device_count_named(self):
        with pytest.raises(PlanError, match="one device per shard"):
            validate_tp(ParallelPlan(tp=2), self._cfg(),
                        engine="ServingEngine", devices=[object()])

    def test_kv_heads_divisibility_named(self):
        with pytest.raises(PlanError, match="kv_heads % tp == 0"):
            validate_tp(ParallelPlan(tp=4),
                        self._cfg(num_kv_heads=2, num_heads=4),
                        engine="ServingEngine")

    def test_vocab_divisibility_named(self):
        with pytest.raises(PlanError, match="vocab_size % tp == 0"):
            validate_tp(ParallelPlan(tp=4), self._cfg(vocab_size=98),
                        engine="ServingEngine")

    def test_num_slots_ring_chunking_named(self, tiny_tp):
        model, _ = tiny_tp
        with pytest.raises(PlanError, match="num_slots % tp == 0"):
            _engine(model, tp=2, num_slots=3)

    def test_prefill_chunk_ring_chunking_named(self):
        with pytest.raises(PlanError, match="prefill_chunk % tp == 0"):
            validate_tp(ParallelPlan(tp=4), self._cfg(),
                        engine="ServingEngine", prefill_chunk=6)

    def test_num_blocks_is_global_not_per_shard(self):
        """The pool-sizing check speaks in GLOBAL blocks — the sharded
        pool keeps one logical free list, num_blocks is never ×tp."""
        with pytest.raises(PlanError, match="GLOBAL"):
            validate_tp(ParallelPlan(tp=2), self._cfg(),
                        engine="ServingEngine", num_blocks=4,
                        max_blocks_per_slot=8)

    def test_sampled_tail_filters_rejected(self, tiny_tp):
        model, _ = tiny_tp
        with pytest.raises(PlanError, match="top_k"):
            _engine(model, tp=2, temperature=0.7, top_k=3)

    def test_decode_engine_sampled_rejected(self, tiny_tp):
        model, _ = tiny_tp
        with pytest.raises(ValueError, match="greedy"):
            DecodeEngine(model, temperature=0.7,
                         plan=ParallelPlan(tp=2))

    def test_spec_with_temperature_rejected_eagerly(self, tiny_tp):
        """serve(draft=...) under tp composes only the greedy verify
        tail — a sampled spec serve fails BEFORE any dispatch."""
        model, params = tiny_tp
        eng = _engine(model, tp=2, temperature=0.0)
        eng.temperature = 0.7  # past the constructor on purpose
        with pytest.raises(ValueError, match="plan.tp"):
            eng.serve(params, _reqs(1), key=K,
                      draft=NGramDrafter(k=2))


class TestTPServingParity:
    """The tentpole witness: tp shards serve the SAME tokens as tp=1
    across the full churn schedule, zero-recompile, leak-free."""

    @pytest.mark.parametrize("tp", [2, 4])
    def test_churn_schedule_bitwise_vs_tp1(self, tiny_tp, tp):
        model, params = tiny_tp
        reqs = _reqs(7)
        base = _toks(_engine(model).serve(params, _reqs(7)))
        eng = _engine(model, tp=tp)
        sched = eng.make_scheduler()
        done = eng.serve(params, reqs, scheduler=sched)
        assert _toks(done) == base
        assert eng.prefill_chunk._cache_size() == 1, "prefill re-traced"
        assert eng.decode_step._cache_size() == 1, "decode re-traced"
        # free list exactly restored: the only live blocks are the
        # prefix cache's warm residents; reclaiming them recovers the
        # fresh pool block-for-block
        alloc = sched.allocator
        alloc.check_accounting()
        assert alloc.leaked == 0
        assert alloc.num_live == alloc.num_resident
        sched.prefix_cache.clear()
        assert alloc.num_live == 0
        assert alloc.num_free == eng.num_blocks - 1

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    @pytest.mark.parametrize("program", ["prefill_chunk", "decode_step",
                                         "spec_step"])
    def test_one_body_one_dispatch_vs_tp1(self, tiny_tp, program,
                                          kv_dtype):
        """The seam's pin: each program with a tp form exists as ONE
        body (no ``*_body_tp`` attribute on either engine — the same
        body runs inside ``shard_map`` over the tp layer math), and one
        dispatch of it at tp = 2 returns the tokens of tp = 1, on the
        float pool and the int8 pool."""
        model, params = tiny_tp
        engines = {tp: _engine(model, tp=tp, kv_dtype=kv_dtype)
                   for tp in (1, 2)}
        for eng in (*engines.values(), DecodeEngine(model),
                    DecodeEngine(model, plan=ParallelPlan(tp=2))):
            twins = [n for n in dir(eng) if n.endswith("_body_tp")]
            assert not twins, f"{type(eng).__name__} grew twins: {twins}"
        S, nb = 4, engines[1].max_blocks_per_slot
        # slots 0..2 live on distinct blocks, slot 3 dead (all writes
        # must land in the dead block under both layer maths)
        tables = np.zeros((S, nb), np.int32)
        tables[:3] = 1 + np.arange(3 * nb).reshape(3, nb)
        lengths = np.asarray([5, 9, 1, 0], np.int32)
        got = {}
        for tp, eng in engines.items():
            rng = np.random.default_rng(5)  # the same operands for both
            p, pool = eng._prepare_params(params), eng.init_pool()
            # a warm prefix under every program: one prompt chunk per
            # live slot through the engine's own prefill
            for i in range(3):
                pool, tok, _ = eng.prefill_chunk(
                    p, pool, jnp.asarray(tables[i]),
                    jnp.asarray(rng.integers(0, 96, 16), jnp.int32),
                    jnp.int32(0), jnp.int32(lengths[i]), K)
            if program == "prefill_chunk":
                got[tp] = [int(tok)]
            elif program == "decode_step":
                _, toks, _ = eng.decode_step(
                    p, pool, jnp.asarray(tables),
                    jnp.asarray([3, 7, 11, 0], jnp.int32),
                    jnp.asarray(lengths + (lengths > 0)), K)
                got[tp] = np.asarray(toks)[:3].tolist()
            else:
                tokens = jnp.asarray(rng.integers(0, 96, (S, 3)), jnp.int32)
                _, acc, nxt = eng.spec_step(
                    p, pool, jnp.asarray(tables), tokens,
                    jnp.asarray(lengths + (lengths > 0)), tokens[:, 1:], K)
                got[tp] = (np.asarray(acc)[:3].tolist(),
                           np.asarray(nxt)[:3].tolist())
        assert got[2] == got[1]

    def test_spec_rounds_bitwise_vs_plain(self, tiny_tp):
        """Speculative serving under tp: greedy output token-identical
        to the plain tp engine AND to tp=1, spec cache pinned at 1."""
        model, params = tiny_tp
        base = _toks(_engine(model).serve(params, _reqs(5, seed=9)))
        eng = _engine(model, tp=2)
        done = eng.serve(params, _reqs(5, seed=9),
                         draft=NGramDrafter(k=2))
        assert _toks(done) == base
        assert eng.spec_step._cache_size() == 1
        assert eng.decode_step._cache_size() <= 1  # spec replaces it
        assert eng.last_stats.spec_rounds > 0  # rounds actually ran

    def test_int8_pool_bitwise_vs_tp1_int8(self, tiny_tp):
        """The quantized pool shards the same way: pmax-composed amax
        scales make the int8 rows bitwise those of the unsharded pool,
        so tokens match the tp=1 int8 engine exactly."""
        model, params = tiny_tp
        base = _toks(_engine(model, kv_dtype="int8").serve(
            params, _reqs(5, seed=4)))
        eng = _engine(model, tp=2, kv_dtype="int8")
        done = eng.serve(params, _reqs(5, seed=4))
        assert _toks(done) == base
        assert eng.decode_step._cache_size() == 1

    def test_hot_swap_under_tp(self, tiny_tp):
        """Weight hot-swap composes with tp: equal-weights swap is
        token-identical with caches pinned (the swapped tree re-shards
        through the same committed layout), and different weights
        actually serve."""
        model, params = tiny_tp
        reqs = lambda: [Request(rid=0, prompt=np.zeros(4, np.int32),  # noqa: E731
                                max_new_tokens=12)]
        base = _toks(_engine(model, tp=2).serve(params, reqs()))
        eng = _engine(model, tp=2)
        clone = jax.tree.map(lambda x: jnp.array(x), params)
        eng.request_swap(clone, at_step=4, source="test-ckpt")
        done = eng.serve(params, reqs())
        assert _toks(done) == base
        assert eng.last_stats.swaps == 1
        assert eng.decode_step._cache_size() == 1
        eng2 = _engine(model, tp=2)
        eng2.request_swap(jax.tree.map(lambda x: x + 0.5, params),
                          at_step=4)
        jolted = eng2.serve(params, reqs())
        assert _toks(jolted) != base  # the new weights really serve
        assert eng2.decode_step._cache_size() == 1


class TestDecodeEngineTP:
    """The fixed-batch engine under tp: generate() bitwise vs tp=1,
    plain and speculative, every jitted body compiled once."""

    @pytest.mark.parametrize("tp", [2, 4])
    def test_generate_bitwise_vs_tp1(self, tiny_tp, tp):
        model, params = tiny_tp
        prompts = np.asarray(
            jr.randint(jr.fold_in(K, 2), (2, 9), 0, 96), np.int32)
        want = np.asarray(
            DecodeEngine(model).generate(params, jnp.asarray(prompts),
                                         10))
        eng = DecodeEngine(model, plan=ParallelPlan(tp=tp))
        got = np.asarray(eng.generate(params, jnp.asarray(prompts), 10))
        np.testing.assert_array_equal(got, want)
        assert eng.prefill._cache_size() == 1
        assert eng.decode_step._cache_size() == 1

    def test_speculative_generate_bitwise(self, tiny_tp):
        model, params = tiny_tp
        prompts = np.asarray(
            jr.randint(jr.fold_in(K, 6), (1, 12), 0, 96), np.int32)
        want = np.asarray(
            DecodeEngine(model).generate(params, jnp.asarray(prompts),
                                         12))
        eng = DecodeEngine(model, plan=ParallelPlan(tp=2))
        got = np.asarray(eng.generate(params, jnp.asarray(prompts), 12,
                                      draft=NGramDrafter(k=2)))
        np.testing.assert_array_equal(got, want)
        assert eng.spec_verify_step._cache_size() == 1


class TestDisaggHandoff:
    """Prefill role → KV stream → decode role: content-addressed block
    transfer riding the PrefixCache keys, digest-verified end to end,
    decode output token-identical to the monolithic engine."""

    def _hand_reqs(self, n=4, seed=7):
        rng = np.random.default_rng(seed)
        return [Request(
            rid=i,
            prompt=np.asarray(rng.integers(0, 96,
                                           int(rng.integers(18, 50))),
                              np.int32),
            max_new_tokens=int(rng.integers(3, 9)))
            for i in range(n)]

    @pytest.mark.parametrize("tp", [1, 2])
    def test_roundtrip_token_identical(self, tiny_tp, tmp_path, tp):
        model, params = tiny_tp
        B = 8
        mono = _toks(_engine(model, tp=tp).serve(params,
                                                 self._hand_reqs()))
        # prefill role: one token each (its TTFT), warm pool + cache
        ep = _engine(model, tp=tp)
        sp = ep.make_scheduler()
        pre = ep.serve(params, prefill_requests(self._hand_reqs()),
                       scheduler=sp)
        assert all(len(r.tokens) == 1 for r in pre)
        handoffs = [export_handoff(ep.last_pool, sp, r, block_size=B)
                    for r in pre]
        for h, r in zip(handoffs, pre):
            assert len(h.blocks) == len(r.prompt) // B
        d = str(tmp_path / "handoff")
        nbytes = write_handoff(d, handoffs)
        assert nbytes == sum(h.nbytes for h in handoffs) > 0
        streamed = read_handoff(d)
        # the streamed digests ARE the source pool's: recompute each
        # block's digest from the PREFILL pool rows the cache chain
        # names and compare to what crossed the wire
        cache = sp.prefix_cache
        for h, s in zip(handoffs, streamed):
            chain = cache.match(h.prompt, count=False)
            for e, blk in zip(chain, s.blocks):
                src = {name: np.asarray(ep.last_pool[name][:, e.block_id])
                       for name in ep.last_pool}
                assert block_digest(src) == blk.digest
                for name in src:
                    np.testing.assert_array_equal(blk.arrays[name], src[name])
        # decode role: ingest into a FRESH engine's pool + cache
        ed = _engine(model, tp=tp)
        sd = ed.make_scheduler()
        pool, stats = ingest_handoff(ed.init_pool(), sd, streamed)
        assert stats.skipped == 0
        assert stats.blocks == stats.digests_verified \
            == sum(len(h.blocks) for h in streamed)
        done = ed.serve(params, self._hand_reqs(), scheduler=sd,
                        pool=pool)
        assert _toks(done) == mono
        # admission really hit the streamed chain (prefill collapsed
        # to at most the one block holding the final prompt token —
        # admission always keeps >=1 token to produce the first logit)
        for h, r in zip(streamed, sorted(done, key=lambda r: r.rid)):
            assert r.prefix_hit_blocks \
                == min(len(h.blocks), (len(r.prompt) - 1) // B)
        assert ed.prefill_chunk._cache_size() == 1
        assert ed.decode_step._cache_size() == 1

    def test_corrupted_payload_is_loud(self, tiny_tp, tmp_path):
        model, params = tiny_tp
        ep = _engine(model)
        sp = ep.make_scheduler()
        pre = ep.serve(params, prefill_requests(self._hand_reqs(2)),
                       scheduler=sp)
        handoffs = [export_handoff(ep.last_pool, sp, r, block_size=8)
                    for r in pre]
        d = str(tmp_path / "h")
        write_handoff(d, handoffs)
        victim = next(f for f in sorted(os.listdir(d))
                      if f.endswith(".bin"))
        raw = bytearray(open(os.path.join(d, victim), "rb").read())
        raw[0] ^= 0xFF
        open(os.path.join(d, victim), "wb").write(bytes(raw))
        with pytest.raises(ValueError, match="digest mismatch"):
            read_handoff(d)

    def test_manifest_framing_is_validated(self, tiny_tp, tmp_path):
        with pytest.raises(FileNotFoundError, match="manifest"):
            read_handoff(str(tmp_path / "nowhere"))
        d = tmp_path / "junk"
        d.mkdir()
        (d / "manifest.json").write_text(json.dumps(
            {"format": "something.else", "version": 1, "requests": []}))
        with pytest.raises(ValueError, match="format"):
            read_handoff(str(d))
        (d / "manifest.json").write_text(json.dumps(
            {"format": "apex_tpu.kv_handoff", "version": 99,
             "requests": []}))
        with pytest.raises(ValueError, match="version"):
            read_handoff(str(d))

    def test_export_before_prefill_is_loud(self, tiny_tp):
        model, _ = tiny_tp
        eng = _engine(model)
        sched = eng.make_scheduler()
        with pytest.raises(ValueError, match="no cached blocks"):
            export_handoff(eng.init_pool(), sched, self._hand_reqs(1)[0],
                           block_size=8)

    def test_handoff_event_one_trace_id_across_roles(self, tiny_tp,
                                                     tmp_path):
        """The lifecycle witness: the export leg (prefill engine) and
        the ingest leg (decode engine) emit ``handoff`` events carrying
        the SAME request trace id — the id travels inside the payload."""
        model, params = tiny_tp
        ep = _engine(model)
        sp = ep.make_scheduler()
        tel_p = ServeTelemetry(slots=4, collect_events=True)
        pre = ep.serve(params, prefill_requests(self._hand_reqs(2)),
                       scheduler=sp, telemetry=tel_p)
        handoffs = [export_handoff(ep.last_pool, sp, r, block_size=8,
                                   telemetry=tel_p)
                    for r in pre]
        assert all(h.trace_id for h in handoffs)  # minted at submit
        d = str(tmp_path / "h")
        write_handoff(d, handoffs)
        ed = _engine(model)
        sd = ed.make_scheduler()
        tel_d = ServeTelemetry(slots=4, collect_events=True)
        ingest_handoff(ed.init_pool(), sd, read_handoff(d),
                       telemetry=tel_d)
        exp = {e["rid"]: e for e in tel_p.events
               if e.get("phase") == "handoff"}
        ing = {e["rid"]: e for e in tel_d.events
               if e.get("phase") == "handoff"}
        assert set(exp) == set(ing) == {r.rid for r in pre}
        for rid in exp:
            assert exp[rid]["handoff_role"] == "export"
            assert ing[rid]["handoff_role"] == "ingest"
            assert exp[rid]["trace_id"] == ing[rid]["trace_id"]
            assert exp[rid]["blocks"] == ing[rid]["blocks"] > 0
            assert exp[rid]["transfer_bytes"] \
                == ing[rid]["transfer_bytes"] > 0
        assert tel_p.handoffs == tel_d.handoffs == 2
        assert tel_d.handoff_transfer_ms > 0

    def test_handoff_event_validates_through_schema(self):
        rec = {"schema": monitor.SCHEMA_VERSION, "kind": "serve_event",
               "rid": 0, "phase": "handoff", "at_s": 0.1,
               "handoff_role": "ingest", "blocks": 3,
               "transfer_bytes": 4096, "dur_ms": 1.25,
               "trace_id": "req-abc"}
        assert monitor.validate(rec) == []
        rec["handoff_role"] = "sideways"
        assert monitor.validate(rec)

    def test_bad_role_is_loud(self):
        tel = ServeTelemetry(slots=2)
        with pytest.raises(ValueError, match="export|ingest"):
            tel.on_handoff(0, "sideways", 1, 10, 0.0)


class TestTPServeRecord:
    """The ``tp_serve`` artifact: closed schema, honesty rule, forced
    CLI dispatch, report line, bench-history series — the same drift
    battery every status record in the repo carries."""

    def _ok_fields(self):
        return dict(tp=2, tokens_per_s=120.0,
                    baseline_tokens_per_s=180.0,
                    ttft_ms_prefill_role=12.5, ttft_ms_monolithic=14.0,
                    handoff_blocks=11, handoff_transfer_bytes=180224,
                    handoff_transfer_ms=3.5, digests_verified=11,
                    collective_ppermute_calls=24,
                    collective_ppermute_bytes=55296,
                    decode_steps=16, collective_bytes_per_step=6144.0,
                    greedy_parity=True, handoff_parity=True,
                    jit_cache_ok=True, kv_dtype="float", requests=8,
                    num_blocks=33, pool_mb_per_shard=0.25,
                    pool_mb_total=0.5)

    def test_ok_record_validates(self):
        rec = monitor.MetricsRegistry().emit_tp_serve(
            "OK", **self._ok_fields())
        assert monitor.validate(rec) == []

    def test_junk_key_fails_closed_schema(self):
        rec = monitor.MetricsRegistry().emit_tp_serve(
            "OK", **self._ok_fields())
        rec["junk_key"] = 1
        assert any("unexpected key" in e for e in monitor.validate(rec))

    def test_nan_in_ok_fails(self):
        with pytest.raises(ValueError, match="non-finite"):
            monitor.MetricsRegistry().emit_tp_serve(
                "OK", tokens_per_s=float("nan"))
        rec = monitor.MetricsRegistry().emit_tp_serve(
            "OK", **self._ok_fields())
        rec["handoff_transfer_ms"] = float("nan")
        assert any("non-finite" in e for e in monitor.validate(rec))

    def test_reasonless_skip_fails(self):
        with pytest.raises(ValueError, match="reason"):
            monitor.MetricsRegistry().emit_tp_serve("SKIP")
        rec = monitor.MetricsRegistry().emit_tp_serve(
            "SKIP", reason="cpu smoke")
        del rec["reason"]
        assert any("reason" in e for e in monitor.validate(rec))

    def test_validator_cli_forced_and_content_dispatch(self, tmp_path):
        rec = monitor.MetricsRegistry().emit_tp_serve(
            "OK", **self._ok_fields())
        good = tmp_path / "tp_serve.json"
        good.write_text(json.dumps(rec))
        assert validate_metrics.main(["--tp-serve", str(good)]) == 0
        assert validate_metrics.main([str(good)]) == 0  # content
        # a file that lost its kind fails AS a tp_serve artifact
        bad = tmp_path / "lost.json"
        bad.write_text(json.dumps(
            {k: v for k, v in rec.items() if k != "kind"}))
        assert validate_metrics.main(["--tp-serve", str(bad)]) == 1
        junk = tmp_path / "junk.json"
        junk.write_text(json.dumps(dict(rec, junk=1)))
        assert validate_metrics.main(["--tp-serve", str(junk)]) == 1

    def test_report_renders_tp_serve_line(self):
        rec = monitor.MetricsRegistry().emit_tp_serve(
            "OK", **self._ok_fields())
        summary = monitor.aggregate([rec])
        assert summary["tp_serve"]["tp"] == 2
        from apex_tpu.monitor.report import render
        text = render(summary)
        assert "tp-serve" in text and "tp=2" in text
        assert "handoff" in text
        skip = monitor.aggregate([monitor.MetricsRegistry().emit_tp_serve(
            "SKIP", reason="cpu smoke")])
        assert "SKIP(cpu smoke)" in render(skip)

    def test_timeline_folds_handoff_legs(self):
        """A merged two-role stream: the row carries both legs' roles,
        block count, and summed bytes; the rendered table shows them."""
        from apex_tpu.monitor.report import (format_serve_timeline,
                                             serve_timeline)
        mk = lambda role: {"kind": "serve_event", "rid": 0,  # noqa: E731
                           "phase": "handoff", "at_s": 0.1,
                           "handoff_role": role, "blocks": 3,
                           "transfer_bytes": 2048}
        tl = serve_timeline([
            {"kind": "serve_event", "rid": 0, "phase": "submit",
             "at_s": 0.0, "prompt_len": 24}, mk("export"), mk("ingest")])
        (row,) = tl["requests"]
        assert row["handoff_roles"] == ["export", "ingest"]
        assert row["handoff_blocks"] == 3
        assert row["handoff_bytes"] == 4096
        assert "handoff export+ingest" in format_serve_timeline(tl)

    def test_bench_history_series(self):
        """An OK tp_serve record gates BOTH series: tokens/s
        (higher-is-better) and handoff_transfer_ms (lower-is-better,
        percent drift); a SKIP record claims nothing."""
        ok = monitor.MetricsRegistry().emit_tp_serve(
            "OK", **self._ok_fields())
        rows = dict((m, v) for m, v, _ in bench_history.extract_all(ok))
        assert rows["tp_serve_tokens_per_s"] == 120.0
        assert rows["tp_serve_handoff_transfer_ms"] == 3.5
        assert ("tp_serve_handoff_transfer_ms"
                in bench_history._LOWER_IS_BETTER_PCT)
        skip = monitor.MetricsRegistry().emit_tp_serve(
            "SKIP", reason="cpu smoke")
        assert bench_history.extract_all(skip) == []
        # pre-tier history: an OK record MISSING the new transfer series
        # (an old-style artifact) still gates its throughput — the new
        # series skips individually, never the whole gate
        old = {k: v for k, v in ok.items()
               if k != "handoff_transfer_ms"}
        names = [m for m, _, _ in bench_history.extract_all(old)]
        assert "tp_serve_tokens_per_s" in names
        assert "tp_serve_handoff_transfer_ms" not in names
