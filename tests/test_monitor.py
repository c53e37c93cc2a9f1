"""Monitor subsystem: registry semantics, hook wiring, schema validation,
report aggregation, artifact honesty.

The fast tier-1 loop for the telemetry layer: emit → validate → report
round-trips in-process (no subprocesses, no mesh), plus the bench-parity
contract — `monitor report` must reproduce tokens/s from the same records
``bench.py`` emits — and the VERDICT r5 weak-#1 regression guard: no
artifact path can put ``nan`` inside a line/record that claims OK.
"""

import importlib.util
import io
import json
import os
import sys

import jax.numpy as jnp
import pytest

from apex_tpu import amp, monitor
from apex_tpu.monitor import report as monitor_report
from apex_tpu.monitor import schema as monitor_schema

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture
def registry():
    buf = io.StringIO()
    reg = monitor.enable(stream=buf)
    try:
        yield reg, buf
    finally:
        monitor.disable()


def records_of(buf: io.StringIO):
    # the clock_sync epoch record framing every enabled stream is
    # covered by tests/test_trace.py; the payload tests here count
    # only the records they emitted
    return [r for r in (json.loads(line)
                        for line in buf.getvalue().splitlines())
            if r.get("kind") != "clock_sync"]


class TestRegistry:
    def test_disabled_hooks_are_noops(self):
        assert not monitor.enabled()
        # none of these may touch their argument while disabled
        monitor.counter("x")
        monitor.gauge("y", 1.0)
        assert monitor.observe_scaler(object()) is None
        assert monitor.observe_grads(object()) is None
        assert monitor.observe_updates(object()) is None
        assert monitor.end_step() is None
        with monitor.timer("t"):
            pass

    def test_counters_gauges_timers(self, registry):
        reg, _ = registry
        reg.counter("c")
        reg.counter("c", 2)
        reg.gauge("g", 3.5)
        reg.gauge("g", 4.5)  # last value wins
        with reg.timer("t"):
            pass
        assert reg.counters["c"] == 3
        assert reg.gauges["g"] == 4.5
        assert reg.timers["t"][0] == 1
        assert reg.timers["t"][1] >= 0

    def test_step_records_carry_deltas(self, registry):
        reg, buf = registry
        reg.counter("collective/psum[dp]_calls", 5)
        reg.begin_step()
        reg.counter("collective/psum[dp]_calls", 2)
        rec = reg.end_step(tokens=128, dur_s=0.5)
        # only the in-window delta, not the lifetime total
        assert rec["counters"] == {"collective/psum[dp]_calls": 2}
        assert rec["step"] == 0
        reg.begin_step()
        rec2 = reg.end_step(dur_s=0.25)
        assert rec2["step"] == 1
        assert rec2["counters"] == {}
        assert len(records_of(buf)) == 2

    def test_counters_total_survive_pre_step_counting(self, registry):
        """Trace-time collective counts land during warm-up, BEFORE the
        first step window — the lifetime totals in the step record are how
        they reach the report."""
        reg, _ = registry
        reg.counter("collective/ppermute[pp]_calls", 11)  # "during tracing"
        reg.begin_step()
        rec = reg.end_step(dur_s=0.1)
        assert rec["counters"] == {}  # nothing inside the window
        assert rec["counters_total"]["collective/ppermute[pp]_calls"] == 11
        from apex_tpu.monitor.report import aggregate

        summary = aggregate([rec])
        assert summary["collectives"]["ppermute[pp]"]["calls"] == 11

    def test_repeated_end_step_does_not_double_count(self, registry):
        reg, _ = registry
        reg.begin_step()
        reg.counter("amp/overflow_steps", 1)
        rec1 = reg.end_step(dur_s=0.1)
        rec2 = reg.end_step(dur_s=0.1)  # no begin_step: fresh baseline
        assert rec1["counters"] == {"amp/overflow_steps": 1}
        assert rec2["counters"] == {}

    def test_enable_truncates_by_default(self, tmp_path):
        path = tmp_path / "events.jsonl"
        for _ in range(2):
            reg = monitor.enable(str(path))
            reg.emit_event("run")
            monitor.disable()
        # one run, one file: each enable() opens with its clock_sync
        assert len(path.read_text().splitlines()) == 2
        reg = monitor.enable(str(path), append=True)
        reg.emit_event("run")
        monitor.disable()
        assert len(path.read_text().splitlines()) == 4

    def test_report_aggregates_last_run_of_appended_file(self, tmp_path):
        from apex_tpu.monitor.report import aggregate, read_records

        path = tmp_path / "events.jsonl"
        for best_dur, tokens in ((0.01, 100), (0.02, 100)):
            reg = monitor.enable(str(path), append=True)
            reg.emit_meta(device_kind="cpu")
            reg.begin_step()
            reg.end_step(dur_s=best_dur, tokens=tokens)
            monitor.disable()
        summary = aggregate(read_records(path.read_text().splitlines()))
        # the stale (faster) first run must NOT leak into the headline
        assert summary["runs_in_file"] == 2
        assert summary["num_steps"] == 1
        assert summary["tokens_per_s"]["best"] == pytest.approx(100 / 0.02)

    def test_rank_tagging(self, registry):
        from apex_tpu.utils.logging import set_rank_info

        reg, _ = registry
        set_rank_info("dp0/pp1/cp0/tp0")
        try:
            rec = reg.emit_event("x")
        finally:
            set_rank_info("")
        assert rec["rank"] == "dp0/pp1/cp0/tp0"
        assert isinstance(rec["process"], int)

    def test_enable_from_env_path(self, tmp_path, monkeypatch):
        path = tmp_path / "events.jsonl"
        monkeypatch.setenv("APEX_TPU_MONITOR", str(path))
        reg = monitor.enable_from_env()
        try:
            assert reg is not None
            reg.emit_event("hello")
        finally:
            monitor.disable()
        assert monitor.validate_jsonl(path.read_text().splitlines()) == []


class TestHonesty:
    def test_success_record_with_nan_refused(self, registry):
        reg, _ = registry
        with pytest.raises(ValueError, match="non-finite"):
            reg.emit("gate", name="g", ok=True,
                     metrics={"loss": float("nan")})

    def test_ok_status_with_inf_refused(self, registry):
        reg, _ = registry
        with pytest.raises(ValueError, match="non-finite"):
            reg.emit("event", name="e", status="OK", value=float("inf"))

    def test_non_success_records_may_carry_nonfinite(self, registry):
        reg, buf = registry
        reg.begin_step()
        reg.end_step(dur_s=0.1, loss=float("nan"))  # diverged loss: allowed
        (rec,) = records_of(buf)
        assert rec["loss"] == "nan"  # stringified — the stream stays JSON

    def test_gate_metrics_rejects_nonfinite(self):
        with pytest.raises(ValueError, match="skipped"):
            monitor.gate_metrics({"x": float("nan")})

    def test_gate_metrics_skip_objects(self):
        out = monitor.gate_metrics(
            {"a": 1.5, "b": ("skipped", "needs n % 16 == 0")})
        assert out == {"a": 1.5,
                       "b": {"skipped": True, "reason": "needs n % 16 == 0"}}

    def test_validator_flags_stringified_nan_in_success(self):
        errs = monitor_schema.validate(
            {"schema": 1, "kind": "gate", "name": "g", "ok": True,
             "metrics": {"loss": "nan"}})
        assert any("nan" in e or "non-finite" in e for e in errs)


class TestHooks:
    def test_observe_scaler_matches_state(self, registry):
        reg, _ = registry
        s = amp.init_loss_scaler("dynamic", init_scale=2.0 ** 16)
        s = amp.update_loss_scaler(s, jnp.asarray(False))
        m = monitor.observe_scaler(s)
        assert m == amp.scaler_metrics(s)
        assert reg.gauges["amp/loss_scale"] == 2.0 ** 15
        assert reg.gauges["amp/skipped_steps_total"] == 1
        # delta counting: the second observation adds only the new overflow
        s = amp.update_loss_scaler(s, jnp.asarray(False))
        monitor.observe_scaler(s)
        assert reg.counters["amp/overflow_steps"] == 1

    def test_observe_grads_and_updates(self, registry):
        reg, _ = registry
        g = {"w": jnp.asarray([3.0, 4.0]), "step": jnp.zeros((), jnp.int32)}
        assert monitor.observe_grads(g) == pytest.approx(5.0)
        assert monitor.observe_updates({"w": jnp.zeros((2,))}) == 0.0
        assert reg.gauges["optim/grad_norm"] == pytest.approx(5.0)
        assert reg.gauges["optim/update_norm"] == 0.0
        out = monitor.observe_optimizer_step(grads=g)
        assert out["grad_norm"] == pytest.approx(5.0)

    def test_bubble_fraction_matches_schedule_theory(self):
        # forward sweep is M*v + S - 1 chunk-ticks, S - 1 of them fill/drain
        # (tests/test_pipeline.py::TestBubbleUtilization measures the same
        # numbers from the schedule's validity masks)
        assert monitor.pipeline_bubble_fraction(8, 4, 1) == pytest.approx(
            3 / 11)
        assert monitor.pipeline_bubble_fraction(8, 4, 4) == pytest.approx(
            3 / 35)

    def test_record_pipeline_schedule(self, registry):
        reg, buf = registry
        monitor.record_pipeline_schedule(
            num_microbatches=8, pipeline_size=4, virtual_chunks=2,
            tick_bytes=1024, axis="pp")
        assert reg.gauges["pipeline/bubble_fraction"] == pytest.approx(3 / 19)
        assert reg.counters["collective/ppermute[pp]_calls"] == 19
        assert reg.counters["collective/ppermute[pp]_bytes"] == 19 * 1024
        (rec,) = records_of(buf)
        assert rec["name"] == "pipeline_schedule" and rec["ticks"] == 19
        assert rec["schedule"] == "1f1b" and rec["overlap_p2p"] is False

    def test_pipeline_cost_model_closed_forms(self, registry):
        """The unit-cost (F=B=W=1) full-step geometry: the autodiff
        schedule pays B+W on every backward tick; zb defers dW into an
        M·v real-items-only sweep — the (S−1)·W drain term is gone."""
        base = monitor.pipeline_cost_model(8, 4, 1, schedule="1f1b")
        zb = monitor.pipeline_cost_model(8, 4, 1, schedule="zb")
        assert base["total_units"] == 33 and zb["total_units"] == 30
        assert base["bubble_fraction"] == pytest.approx(9 / 33)
        assert zb["bubble_fraction"] == pytest.approx(6 / 30)
        # overlap: L=2 — fwd ticks M*v + 2(S-1) + 1, dW sweep unchanged
        ov = monitor.pipeline_cost_model(8, 4, 1, schedule="zb",
                                         overlap_p2p=True)
        assert ov["fwd_ticks"] == 8 + 2 * 3 + 1
        assert ov["bwd_dw_ticks"] == 8
        # recompute priced separately and honestly: zb = 1f1b + M*v
        assert zb["recompute_units"] == base["recompute_units"] + 8
        assert zb["collective_free_ticks"] == 8
        # the schedule-aware gauge/event carry the step bubble
        reg, buf = registry
        monitor.record_pipeline_schedule(
            num_microbatches=8, pipeline_size=4, schedule="zb")
        assert reg.gauges["pipeline/bubble_fraction_step"] == \
            pytest.approx(6 / 30)
        (rec,) = records_of(buf)
        assert rec["schedule"] == "zb"
        assert rec["bwd_dw_ticks"] == 8 and rec["bwd_dx_ticks"] == 11

    def test_count_collective_and_tree_bytes(self, registry):
        reg, _ = registry
        tree = {"a": jnp.zeros((4, 8), jnp.float32),
                "b": jnp.zeros((2,), jnp.bfloat16)}
        nbytes = monitor.tree_bytes(tree)
        assert nbytes == 4 * 8 * 4 + 2 * 2
        monitor.count_collective("psum", bytes=nbytes, axis="dp")
        assert reg.counters["collective/psum[dp]_bytes"] == nbytes


class TestRoundTrip:
    """emit → validate → report, the tier-1 loop of the ISSUE."""

    def _simulate(self, path):
        reg = monitor.enable(str(path))
        try:
            monitor.emit_meta(device_kind="TPU v5p",
                              model_flops_per_token=1e9,
                              batch=4, seq=256)
            monitor.record_pipeline_schedule(
                num_microbatches=8, pipeline_size=4, tick_bytes=64)
            scaler = amp.init_loss_scaler("dynamic", init_scale=2.0 ** 16,
                                          growth_interval=2)
            durs = [0.02, 0.0199, 0.0201, 0.0198]
            # overflow on step 1 (after the baseline observation on step 0),
            # then recovery and growth back at growth_interval=2
            finites = [True, False, True, True]
            for dur, finite in zip(durs, finites):
                monitor.begin_step()
                scaler = amp.update_loss_scaler(scaler, jnp.asarray(finite))
                monitor.observe_scaler(scaler)
                # the pattern a pipelined loop uses: time the blocking
                # fwd/bwd so the report can derive per-tick wall time
                monitor.observe_seconds("pipeline/fwd_bwd", dur * 0.8)
                monitor.end_step(dur_s=dur, tokens=4 * 256, loss=4.5)
            return durs
        finally:
            monitor.disable()

    def test_emit_validate_report(self, tmp_path):
        path = tmp_path / "events.jsonl"
        durs = self._simulate(path)
        lines = path.read_text().splitlines()
        assert monitor.validate_jsonl(lines) == []

        summary = monitor.aggregate(monitor_report.read_records(lines))
        assert summary["num_steps"] == 4
        # tokens/s headline = best step, the bench's min-of-passes rule
        expect = 4 * 256 / min(durs)
        assert summary["tokens_per_s"]["best"] == pytest.approx(
            expect, rel=5e-3)
        # MFU via the shared spec-peak table
        peak = monitor.PEAK_FLOPS_BY_DEVICE["TPU v5p"]
        assert summary["mfu"] == pytest.approx(1e9 * expect / peak, rel=1e-6)
        assert summary["overflow_rate"] == pytest.approx(1 / 4)
        assert summary["pipeline"]["bubble_fraction"] == pytest.approx(
            3 / 11, abs=1e-5)
        # per-(microbatch, stage) wall time: timed fwd/bwd calls / ticks
        expect_tick = sum(d * 0.8 for d in durs) / 4 / 11
        assert summary["pipeline"]["per_tick_wall_s"] == pytest.approx(
            expect_tick, rel=1e-6)
        # scaler halved on the overflow, then grew back at the interval
        assert summary["loss_scale_last"] == 2.0 ** 16

    def test_report_cli(self, tmp_path, capsys):
        path = tmp_path / "events.jsonl"
        self._simulate(path)
        assert monitor_report.main(["report", str(path)]) == 0
        out = capsys.readouterr().out
        assert "tokens/s" in out and "overflow" in out and "bubble" in out
        assert monitor_report.main(["report", str(path), "--json"]) == 0
        summary = json.loads(capsys.readouterr().out)
        assert summary["num_steps"] == 4


def _load_validate_tool():
    tool_path = os.path.join(os.path.dirname(__file__), "..", "tools",
                             "validate_metrics.py")
    spec = importlib.util.spec_from_file_location("validate_metrics",
                                                  tool_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestValidateTool:
    def test_clean_stream_passes(self, tmp_path):
        tool = _load_validate_tool()
        path = tmp_path / "events.jsonl"
        reg = monitor.enable(str(path))
        try:
            reg.emit_event("x")
            reg.begin_step()
            reg.end_step(dur_s=0.1)
        finally:
            monitor.disable()
        assert tool.validate_file(str(path)) == []

    def test_bench_wrapper_passes(self, tmp_path):
        tool = _load_validate_tool()
        wrapper = {"n": 5, "rc": 0, "tail": "...",
                   "parsed": {"metric": "m", "value": 1.0, "unit": "u"}}
        p = tmp_path / "BENCH_x.json"
        p.write_text(json.dumps(wrapper))
        assert tool.validate_file(str(p)) == []

    def test_nan_inside_ok_line_fails(self, tmp_path):
        """The VERDICT r5 weak-#1 artifact shape must be flagged."""
        tool = _load_validate_tool()
        wrapper = {"n_devices": 8, "rc": 0, "ok": True,
                   "tail": "dryrun_multichip(n=8): loss=4.37 "
                           "tpcp_4axis_loss=nan OK\n"}
        p = tmp_path / "MULTICHIP_x.json"
        p.write_text(json.dumps(wrapper))
        problems = tool.validate_file(str(p))
        assert problems and "non-finite" in problems[0]

    def test_skip_token_inside_ok_line_passes(self, tmp_path):
        tool = _load_validate_tool()
        wrapper = {"n_devices": 8, "rc": 0, "ok": True,
                   "tail": "dryrun_multichip(n=8): loss=4.37 "
                           "tpcp_4axis_loss=SKIP(needs-n%16==0) OK\n"}
        p = tmp_path / "MULTICHIP_x.json"
        p.write_text(json.dumps(wrapper))
        assert tool.validate_file(str(p)) == []

    def test_cli_over_fresh_stream_with_decode_records(self, tmp_path):
        """Tier-1 schema-drift gate (ISSUE 2 satellite): the validator CLI
        must pass a freshly emitted stream carrying every record kind —
        including the serving-bench ``decode`` records (OK and SKIP forms)
        — so a schema/emitter drift fails in-suite, not at bench time."""
        tool = _load_validate_tool()
        path = tmp_path / "events.jsonl"
        monitor.enable(str(path))
        try:
            monitor.emit_meta(device_kind="cpu", model_flops_per_token=1e6)
            monitor.begin_step()
            monitor.end_step(dur_s=0.01, tokens=128)
            monitor.emit_decode(
                "OK", tokens_per_s=5000.0, prefill_ms=12.5, spread_pct=0.4,
                naive_tokens_per_s=400.0, vs_naive=12.5, batch=4,
                prompt_len=64, new_tokens=32)
            monitor.emit_decode(
                "SKIP", reason="no TPU attached",
                vs_naive=("skipped", "no TPU attached"))
        finally:
            monitor.disable()
        assert tool.main([str(path)]) == 0

        # drift guard: an OK decode record carrying nan (hand-forged past
        # the emitter) must fail the CLI
        bad = next(r for r in (json.loads(ln)
                               for ln in path.read_text().splitlines())
                   if r.get("kind") == "decode" and r["status"] == "OK")
        bad["tokens_per_s"] = "nan"
        bad_path = tmp_path / "bad.jsonl"
        bad_path.write_text(json.dumps(bad) + "\n")
        assert tool.main([str(bad_path)]) == 1

    def test_driver_envelope_validates(self, tmp_path):
        """A driver envelope (``{"parsed": {...bench result...}}``) is
        unwrapped and its payload schema-checked."""
        tool = _load_validate_tool()
        result = {"metric": "gpt_medium_train_step_throughput",
                  "value": 1000.0, "unit": "tokens/s/chip",
                  "vs_baseline": 2.0, "mfu": 0.5, "model_tflops": 100.0,
                  "donated": True, "spread_pct": 0.1,
                  "pass_times_ms": [1.0, 1.0, 1.0]}
        good = tmp_path / "BENCH_r01.json"
        good.write_text(json.dumps({"n": 1, "rc": 0, "parsed": result}))
        assert tool.validate_file(str(good)) == []
        bad = tmp_path / "BENCH_r02.json"
        bad.write_text(json.dumps(
            {"n": 2, "rc": 0, "parsed": dict(result, value="nan")}))
        assert tool.validate_file(str(bad)) != []


class TestLoggingSatellite:
    """The logging fixes riding with the monitor PR: APEX_TPU_LOG_LEVEL is
    re-applied on every get_logger call, and the rank fallback can come
    from jax.process_index() once the backend is up."""

    def test_env_level_reapplied_after_first_configuration(self, monkeypatch):
        import logging

        from apex_tpu.utils.logging import get_logger

        name = "apex_tpu.test_monitor.env_level"
        monkeypatch.delenv("APEX_TPU_LOG_LEVEL", raising=False)
        assert get_logger(name).level == logging.WARNING
        monkeypatch.setenv("APEX_TPU_LOG_LEVEL", "DEBUG")
        assert get_logger(name).level == logging.DEBUG  # took effect late
        monkeypatch.setenv("APEX_TPU_LOG_LEVEL", "ERROR")
        assert get_logger(name).level == logging.ERROR

    def test_explicit_level_pins_against_env(self, monkeypatch):
        import logging

        from apex_tpu.utils.logging import get_logger

        name = "apex_tpu.test_monitor.pinned"
        get_logger(name, level=logging.INFO)
        monkeypatch.setenv("APEX_TPU_LOG_LEVEL", "CRITICAL")
        assert get_logger(name).level == logging.INFO

    def test_process_index_from_jax_when_backend_up(self):
        import jax

        import apex_tpu.utils.logging as log_util

        log_util._PROCESS_INDEX = None  # drop the cache
        try:
            jax.devices()  # backend definitely initialized now
            assert log_util.process_index() == jax.process_index()
        finally:
            log_util._PROCESS_INDEX = None

    def test_rank_filter_uses_fallback(self):
        import logging

        from apex_tpu.utils.logging import RankInfoFilter, get_rank_info

        assert get_rank_info() == ""  # no mesh in this test
        record = logging.LogRecord("n", logging.INFO, "p", 1, "m", (), None)
        assert RankInfoFilter().filter(record)
        assert record.rank_info.startswith("p")


class TestGateReporting:
    """__graft_entry__'s gate artifact: SKIP sentinels, schema'd record."""

    def test_report_gate_renders_skips_not_nan(self, capsys):
        import __graft_entry__ as graft

        graft._report_gate(
            4, dp=2, pp=2, tp=1, cp=2,
            loss=4.5, moe_4axis_loss=4.4,
            cp_pipe_loss=4.3,
            t5_loss=18.8,
            tpcp_4axis_loss=graft._SKIP("needs n_devices % 16 == 0"),
            moe_16wide_loss=4.31,
            ring_vs_flash=3e-7,
            ring_bias_vs_flash=graft._SKIP("16-wide respawn timed out"),
            zb_vs_1f1b=0.0,
        )
        out = capsys.readouterr().out
        gate_line = [l for l in out.splitlines() if l.endswith(" OK")][0]
        assert "nan" not in gate_line
        assert "tpcp_4axis_loss=SKIP(needs-n_devices-%-16-==-0)" in gate_line
        assert "ring_bias_vs_flash=SKIP(16-wide-respawn-timed-out)" in \
            gate_line
        assert "zb_vs_1f1b=0.00e+00" in gate_line  # the ISSUE-8 witness
        json_line = [l for l in out.splitlines()
                     if l.startswith("MULTICHIP_GATE ")][0]
        record = json.loads(json_line[len("MULTICHIP_GATE "):])
        assert monitor.validate(record) == []
        assert record["metrics"]["tpcp_4axis_loss"] == {
            "skipped": True, "reason": "needs n_devices % 16 == 0"}
        assert record["metrics"]["loss"] == 4.5

    def test_report_gate_refuses_nan_measurement(self, capsys):
        import __graft_entry__ as graft

        with pytest.raises(ValueError, match="skipped"):
            graft._report_gate(
                4, dp=2, pp=2, tp=1, cp=2,
                loss=float("nan"), moe_4axis_loss=4.4, cp_pipe_loss=4.3,
                t5_loss=18.8, tpcp_4axis_loss=graft._SKIP("x"),
                ring_vs_flash=3e-7,
            )
        assert " OK" not in capsys.readouterr().out


class TestTPCollectiveCounts:
    """ISSUE 5 satellite: the tensor-parallel mappings/layers collectives
    emit ``count_collective`` (bytes + axis) like ``all_reduce_gradients``
    and the pipeline ``_rotate`` already do — the tp axis shows up in
    ``monitor report``'s collective traffic line. Counting is trace-time:
    one un-jitted shard_map call registers the counters."""

    def _mesh(self):
        from apex_tpu.parallel import mesh as mesh_lib
        return mesh_lib.make_mesh(tensor_model_parallel_size=4)

    def test_sp_layer_collectives_counted(self, registry):
        import jax.random as jr
        from jax.sharding import PartitionSpec as P

        from apex_tpu.parallel import mesh as mesh_lib
        from apex_tpu.transformer import tensor_parallel as tp_lib

        reg, _ = registry
        mesh = self._mesh()
        col = tp_lib.ColumnParallelLinear(8, 16, tp_size=4, bias=False,
                                          sequence_parallel=True)
        row = tp_lib.RowParallelLinear(16, 8, tp_size=4, bias=False,
                                       sequence_parallel=True)
        x = jr.normal(jr.PRNGKey(0), (4, 2, 8))
        wc = jr.normal(jr.PRNGKey(1), (16, 8))
        wr = jr.normal(jr.PRNGKey(2), (8, 16))
        mesh_lib.shard_map(
            lambda x, wc, wr: row({"weight": wr}, col({"weight": wc}, x)),
            mesh=mesh,
            in_specs=(P("tp"), P("tp", None), P(None, "tp")),
            out_specs=P("tp"),
        )(x, wc, wr)
        c = reg.counters
        assert c.get("collective/all_gather[tp]_calls", 0) >= 1
        assert c.get("collective/all_gather[tp]_bytes", 0) > 0
        assert c.get("collective/psum_scatter[tp]_calls", 0) >= 1
        assert c.get("collective/psum_scatter[tp]_bytes", 0) > 0

    def test_mappings_psum_counted(self, registry):
        import jax
        import jax.random as jr
        from jax.sharding import PartitionSpec as P

        from apex_tpu.parallel import mesh as mesh_lib
        from apex_tpu.transformer import tensor_parallel as tp_lib

        reg, _ = registry
        mesh = self._mesh()
        x = jr.normal(jr.PRNGKey(3), (4, 8))

        def f(x):
            # forward psum (reduce_from) + backward psum (copy_to's VJP)
            y = tp_lib.reduce_from_tensor_model_parallel_region(x, "tp")
            return jax.grad(lambda x: (
                tp_lib.copy_to_tensor_model_parallel_region(x, "tp") ** 2
            ).sum())(y)

        mesh_lib.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P())(x)
        assert reg.counters.get("collective/psum[tp]_calls", 0) >= 2

    def test_overlap_ring_ppermute_counted(self, registry):
        import jax.random as jr
        from jax.sharding import PartitionSpec as P

        from apex_tpu.parallel import mesh as mesh_lib
        from apex_tpu.transformer import tensor_parallel as tp_lib

        reg, _ = registry
        mesh = self._mesh()
        col = tp_lib.ColumnParallelLinear(8, 16, tp_size=4, bias=False,
                                          sequence_parallel=True,
                                          overlap_comm=True)
        x = jr.normal(jr.PRNGKey(4), (4, 2, 8))
        wc = jr.normal(jr.PRNGKey(5), (16, 8))
        mesh_lib.shard_map(
            lambda x, wc: col({"weight": wc}, x), mesh=mesh,
            in_specs=(P("tp"), P("tp", None)), out_specs=P("tp"))(x, wc)
        c = reg.counters
        # tp=4 bidirectional ag ring: 2 fwd + 1 bwd ppermute steps
        assert c.get("collective/ppermute[tp]_calls", 0) >= 3
        assert c.get("collective/ppermute[tp]_bytes", 0) > 0
        # the overlapped path replaced the blocking gather entirely
        assert "collective/all_gather[tp]_calls" not in c


class TestTPOverlapRecords:
    """The ``tp_overlap`` bench record (``bench.py --tp-overlap``):
    overlapped vs blocking boundary collectives — same status/honesty
    contract as the decode and longseq_bias records."""

    def test_emit_roundtrip_and_validation(self, tmp_path):
        path = tmp_path / "events.jsonl"
        monitor.enable(str(path))
        try:
            rec = monitor.emit_tp_overlap(
                "OK", tokens_per_s=61000.0, tokens_per_s_blocking=52000.0,
                vs_blocking=1.173, tp=4, batch=8, seq=1024,
                sequence_parallel=True, spread_pct=0.4,
                pass_times_ms=[134.2, 134.5, 134.9], backend="tpu")
            assert monitor.validate(rec) == []
        finally:
            monitor.disable()
        assert monitor.validate_jsonl(path.read_text().splitlines()) == []

    def test_ok_with_nan_refused_and_skip_needs_reason(self):
        reg = monitor.MetricsRegistry()
        with pytest.raises(ValueError, match="non-finite"):
            reg.emit_tp_overlap("OK", tokens_per_s=float("nan"))
        with pytest.raises(ValueError, match="reason"):
            reg.emit_tp_overlap("SKIP")
        rec = reg.emit_tp_overlap(
            "SKIP", reason="cpu smoke run",
            vs_blocking=("skipped", "cpu smoke run"))
        assert rec["vs_blocking"] == {"skipped": True,
                                      "reason": "cpu smoke run"}
        assert monitor.validate(rec) == []
        # the validator enforces the reason on external streams too
        bare = {k: v for k, v in rec.items() if k != "reason"}
        assert any("reason" in e for e in monitor.validate(bare))

    def test_report_aggregates_and_renders(self):
        reg = monitor.MetricsRegistry()
        ok = reg.emit_tp_overlap(
            "OK", tokens_per_s=61000.0, tokens_per_s_blocking=52000.0,
            vs_blocking=1.173, tp=4, batch=8, seq=1024)
        summary = monitor_report.aggregate([ok])
        assert summary["tp_overlap"]["vs_blocking"] == 1.173
        text = monitor_report.render(summary)
        assert "tp-overlap" in text and "1.17x vs blocking" in text
        skip = reg.emit_tp_overlap("SKIP", reason="no TPU")
        text = monitor_report.render(monitor_report.aggregate([skip]))
        assert "tp-overlap  SKIP(no TPU)" in text


@pytest.mark.slow
class TestTPOverlapBenchLeg:
    def test_bench_tp_overlap_emits_valid_skip_record_off_tpu(
            self, tmp_path):
        """The tp-overlap leg end-to-end at smoke scale: off-TPU it runs
        both impls on the virtual mesh and must print/emit an explicit
        SKIP record — schema-valid, no nan — that the validator CLI
        accepts."""
        import subprocess
        root = os.path.join(os.path.dirname(__file__), "..")
        path = tmp_path / "tpoverlap.jsonl"
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   APEX_TPU_MONITOR=str(path))
        proc = subprocess.run(
            [sys.executable, os.path.join(root, "bench.py"),
             "--tp-overlap"],
            capture_output=True, text=True, env=env, cwd=root, timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        record = json.loads(proc.stdout.strip().splitlines()[-1])
        assert record["kind"] == "tp_overlap"
        assert record["status"] == "SKIP" and record["reason"]
        assert record["tokens_per_s"] > 0
        assert record["tokens_per_s_blocking"] > 0
        assert monitor.validate(record) == []
        tool = _load_validate_tool()
        assert tool.main([str(path)]) == 0


class TestLongseqBiasRecords:
    """The ``longseq_bias`` bench record (``bench.py --longseq-bias``):
    in-kernel bucketed bias vs the materialized baseline — same status/
    honesty contract as the decode record."""

    def test_emit_roundtrip_and_validation(self, tmp_path):
        path = tmp_path / "events.jsonl"
        monitor.enable(str(path))
        try:
            rec = monitor.emit_longseq_bias(
                "OK", tokens_per_s=52000.0,
                tokens_per_s_materialized=31000.0, vs_materialized=1.677,
                hbm_peak_mb=900.5, hbm_peak_materialized_mb=2400.0,
                bias_bytes=768, bias_bytes_materialized=1610612736,
                seq=8192, batch=1, heads=6, head_dim=128, num_buckets=32,
                causal=False, spread_pct=0.3)
            assert monitor.validate(rec) == []
        finally:
            monitor.disable()
        assert monitor.validate_jsonl(path.read_text().splitlines()) == []

    def test_ok_with_nan_refused_and_skip_needs_reason(self):
        reg = monitor.MetricsRegistry()
        with pytest.raises(ValueError, match="non-finite"):
            reg.emit_longseq_bias("OK", tokens_per_s=float("nan"))
        with pytest.raises(ValueError, match="reason"):
            reg.emit_longseq_bias("SKIP")
        rec = reg.emit_longseq_bias(
            "SKIP", reason="no TPU",
            hbm_peak_mb=("skipped", "no memory_stats"))
        assert rec["hbm_peak_mb"] == {"skipped": True,
                                      "reason": "no memory_stats"}
        assert monitor.validate(rec) == []
        # the validator enforces the reason too (external streams)
        bare = {k: v for k, v in rec.items() if k != "reason"}
        assert any("reason" in e for e in monitor.validate(bare))


@pytest.mark.slow
class TestLongseqBiasBenchLeg:
    def test_bench_longseq_bias_emits_valid_skip_record_off_tpu(
            self, tmp_path):
        """The long-seq bias leg end-to-end at smoke scale: off-TPU it
        must print/emit an explicit SKIP record — schema-valid, no nan —
        and the stream must pass the validator CLI."""
        import subprocess
        root = os.path.join(os.path.dirname(__file__), "..")
        path = tmp_path / "longseq.jsonl"
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   APEX_TPU_MONITOR=str(path))
        proc = subprocess.run(
            [sys.executable, os.path.join(root, "bench.py"),
             "--longseq-bias"],
            capture_output=True, text=True, env=env, cwd=root, timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        record = json.loads(proc.stdout.strip().splitlines()[-1])
        assert record["kind"] == "longseq_bias"
        assert record["status"] == "SKIP" and record["reason"]
        assert record["hbm_peak_mb"]["skipped"] is True
        assert monitor.validate(record) == []
        tool = _load_validate_tool()
        assert tool.main([str(path)]) == 0


class TestSpans:
    """The step-anatomy span API (monitor.spans): host enter/exit records
    riding the JSONL stream, named-scope join keys into device traces,
    near-no-op when disabled, ``traced`` honesty inside jit."""

    def test_disabled_span_is_noop(self):
        assert not monitor.enabled()
        with monitor.span("step", step=0):
            pass
        assert monitor.span_path() == ""

    def test_disabled_span_reads_no_clock_and_keeps_its_path(self, monkeypatch):
        from apex_tpu.monitor import spans

        def no_clock():
            raise AssertionError("a span read the clock with no registry")

        monkeypatch.setattr(spans, "monotonic_ns", no_clock)
        with monitor.span("step"):
            with monitor.span("fwd_bwd"):
                assert monitor.span_path() == "step/fwd_bwd"
        assert monitor.span_path() == ""

    def test_each_thread_nests_its_own_spans(self):
        import threading

        inside, leave = threading.Event(), threading.Event()
        seen = {}

        def background():
            with monitor.span("background"):
                seen["background"] = monitor.span_path()
                inside.set()
                leave.wait(10)
            seen["after"] = monitor.span_path()

        t = threading.Thread(target=background)
        t.start()
        assert inside.wait(10)
        # entered while the other thread's span is open, left after it
        with monitor.span("serve_decode"):
            seen["loop"] = monitor.span_path()
            leave.set()
            t.join(10)
            assert monitor.span_path() == "serve_decode"
        assert seen == {"background": "background", "loop": "serve_decode",
                        "after": ""}
        assert monitor.span_path() == ""

    def test_disabled_traced_span_reaches_the_lowered_program(self):
        import jax
        import jax.numpy as jnp

        assert not monitor.enabled()

        def f(x):
            with monitor.span("step"):
                with monitor.span("gpt/attn"):
                    return x * 2

        text = jax.jit(f).lower(jnp.ones(4)).as_text(debug_info=True)
        assert "step/gpt/attn/mul" in text

    def test_host_phase_span_lands_in_the_profilers_own_trace(self, tmp_path):
        import glob

        import jax
        import jax.numpy as jnp
        from jax.profiler import ProfileData

        from apex_tpu import prof

        assert not monitor.enabled()
        f = jax.jit(lambda x: x + 1)

        @jax.jit
        def g(x):
            with monitor.span("traced_only"):
                return x * 2

        with prof.trace(str(tmp_path)):
            with monitor.span("host_phase"):
                with monitor.span("inner"):
                    f(g(jnp.ones(4))).block_until_ready()
        files = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
        assert len(files) == 1
        on_host = {e.name: e.duration_ns
                   for plane in ProfileData.from_file(files[0]).planes
                   if plane.name == "/host:CPU"
                   for line in plane.lines for e in line.events}
        assert on_host["host_phase"] >= on_host["host_phase/inner"] > 0
        # under a JAX trace a span is a scope only: its host time is tracing time
        assert not any("traced_only" in name for name in on_host)

    def test_span_records_path_time_and_attrs(self, registry):
        reg, buf = registry
        with monitor.span("step", step=3):
            assert monitor.span_path() == "step"
            with monitor.span("fwd_bwd"):
                assert monitor.span_path() == "step/fwd_bwd"
        assert monitor.span_path() == ""
        recs = records_of(buf)
        assert [r["name"] for r in recs] == ["step/fwd_bwd", "step"]
        for r in recs:
            assert r["kind"] == "span"
            assert r["dur_ns"] >= 0 and r["t0_ns"] > 0
            assert "traced" not in r  # host phase
            assert monitor.validate(r) == []
        assert recs[1]["step"] == 3
        # nesting: the inner window is inside the outer one
        assert recs[0]["t0_ns"] >= recs[1]["t0_ns"]

    def test_traced_span_is_flagged(self, registry):
        import jax
        import jax.numpy as jnp

        reg, buf = registry

        def f(x):
            with monitor.span("fwd_bwd"):
                return x * 2

        jax.jit(f)(jnp.ones(4))
        spans = [r for r in records_of(buf) if r["kind"] == "span"]
        assert spans and all(s["traced"] is True for s in spans)
        assert all(monitor.validate(s) == [] for s in spans)

    def test_collective_span_attrs_and_none_axis(self, registry):
        import jax.numpy as jnp

        reg, buf = registry
        with monitor.collective_span("psum", jnp.zeros((4, 8)), "tp"):
            pass
        with monitor.collective_span("psum", jnp.zeros((4, 8)), None):
            pass  # tp=1 fallthrough: no record
        spans = [r for r in records_of(buf) if r["kind"] == "span"]
        assert len(spans) == 1
        s = spans[0]
        assert s["name"] == "psum_tp"
        assert s["coll"] == "psum" and s["axis"] == "tp"
        assert s["bytes"] == 4 * 8 * 4

    def test_mappings_emit_collective_spans(self, registry):
        import jax.random as jr
        from jax.sharding import PartitionSpec as P

        from apex_tpu.parallel import mesh as mesh_lib
        from apex_tpu.transformer import tensor_parallel as tp_lib

        reg, buf = registry
        mesh = mesh_lib.make_mesh(tensor_model_parallel_size=4)
        x = jr.normal(jr.PRNGKey(3), (4, 8))
        mesh_lib.shard_map(
            lambda x: tp_lib.reduce_from_tensor_model_parallel_region(
                x, "tp"),
            mesh=mesh, in_specs=P(), out_specs=P())(x)
        spans = [r for r in records_of(buf) if r["kind"] == "span"]
        psums = [s for s in spans if s["name"].endswith("psum_tp")]
        assert psums, spans
        assert psums[0]["coll"] == "psum"
        assert psums[0]["bytes"] > 0
        assert psums[0]["traced"] is True  # shard_map traces the fn

    def test_overlap_ring_emits_ring_span(self, registry):
        import jax.random as jr
        from jax.sharding import PartitionSpec as P

        from apex_tpu.ops.collective_matmul import all_gather_matmul
        from apex_tpu.parallel import mesh as mesh_lib

        reg, buf = registry
        mesh = mesh_lib.make_mesh(tensor_model_parallel_size=4)
        x = jr.normal(jr.PRNGKey(0), (4, 2, 8))
        w = jr.normal(jr.PRNGKey(1), (4, 8))
        mesh_lib.shard_map(
            lambda x, w: all_gather_matmul(x, w, axis_name="tp"),
            mesh=mesh, in_specs=(P("tp"), P("tp", None)),
            out_specs=P(None, None, "tp"))(x, w)
        spans = [r for r in records_of(buf) if r["kind"] == "span"]
        rings = [s for s in spans if "ag_matmul_ring_tp" in s["name"]]
        assert rings, spans
        assert rings[0]["coll"] == "ag_matmul_ring"
        # per-hop payload: the local (1, 2, 8) fp32 shard
        assert rings[0]["bytes"] == 1 * 2 * 8 * 4


class TestProfileRecord:
    """The ``profile`` bench record (``bench.py --profile``): same
    status/honesty contract as decode/longseq_bias/tp_overlap."""

    def test_emit_roundtrip_and_validation(self, tmp_path):
        path = tmp_path / "events.jsonl"
        monitor.enable(str(path))
        try:
            rec = monitor.emit_profile(
                "OK", steps=5, compute_pct=71.2,
                collective_exposed_pct=9.1, bubble_pct=12.4,
                host_gap_pct=7.3, step_wall_ms=177.1,
                tokens_per_s=115000.0, costdb_collective_rows=6,
                costdb_gemm_classes=4, backend="tpu")
            assert monitor.validate(rec) == []
        finally:
            monitor.disable()
        assert monitor.validate_jsonl(path.read_text().splitlines()) == []

    def test_ok_with_nan_refused_and_skip_needs_reason(self):
        reg = monitor.MetricsRegistry()
        with pytest.raises(ValueError, match="non-finite"):
            reg.emit_profile("OK", compute_pct=float("nan"))
        with pytest.raises(ValueError, match="reason"):
            reg.emit_profile("SKIP")
        rec = reg.emit_profile(
            "SKIP", reason="host-only trace",
            compute_pct=("skipped", "host-only trace"))
        assert rec["compute_pct"] == {"skipped": True,
                                      "reason": "host-only trace"}
        assert monitor.validate(rec) == []
        bare = {k: v for k, v in rec.items() if k != "reason"}
        assert any("reason" in e for e in monitor.validate(bare))


def _write_synthetic_trace(tmp_path, events):
    import gzip

    run = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    os.makedirs(run)
    with gzip.open(run / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    return str(tmp_path)


def _anatomy_fixture(tmp_path):
    """One host span stream + one device trace with hand-checkable
    anatomy: step 0 wall 120 us (compute 70, exposed collective 20,
    bubble 10, host gap 20), step 1 wall 100 us (compute 50, exposed 20,
    bubble 10, host gap 20)."""
    meta = [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
    ]
    def X(name, ts, dur, cat=None):
        e = {"ph": "X", "pid": 3, "tid": 3, "ts": ts, "dur": dur,
             "name": name, "args": {}}
        if cat:
            e["args"]["hlo_category"] = cat
        return e
    events = meta + [
        X("step/fwd_bwd/dot.1", 0.0, 60.0),
        X("step/fwd_bwd/all-gather.2", 40.0, 40.0, "all-gather"),
        X("step/optimizer/fusion.3", 90.0, 10.0),
        X("step/fwd_bwd/dot.1", 1000.0, 50.0),
        X("step/fwd_bwd/all-gather.2", 1060.0, 20.0, "all-gather"),
    ]
    logdir = _write_synthetic_trace(tmp_path / "trace", events)
    stream = tmp_path / "events.jsonl"
    reg = monitor.enable(str(stream))
    try:
        for i, dur_us in enumerate((120, 100)):
            reg.emit("span", name="step", step=i,
                     t0_ns=1_000_000 * (1 + i), dur_ns=dur_us * 1000)
        reg.emit("span", name="step/fwd_bwd", t0_ns=1, dur_ns=1,
                 traced=True)
    finally:
        monitor.disable()
    return str(stream), logdir


class TestAnatomyReportCLI:
    """`monitor report --anatomy` must reproduce the per-step breakdown
    from a synthetic host+device fixture exactly (the ISSUE acceptance
    line)."""

    def test_report_anatomy_exact(self, tmp_path, capsys):
        stream, logdir = _anatomy_fixture(tmp_path)
        rc = monitor_report.main(["report", stream, "--anatomy",
                                  "--trace", logdir, "--json"])
        assert rc == 0
        summary = json.loads(capsys.readouterr().out)
        rows = summary["anatomy"]
        assert len(rows) == 2
        r0, r1 = rows
        assert r0["compute_pct"] == pytest.approx(100 * 70 / 120)
        assert r0["collective_exposed_pct"] == pytest.approx(
            100 * 20 / 120)
        assert r0["bubble_pct"] == pytest.approx(100 * 10 / 120)
        assert r0["host_gap_pct"] == pytest.approx(100 * 20 / 120)
        assert r1["compute_pct"] == pytest.approx(50.0)
        assert r1["collective_exposed_pct"] == pytest.approx(20.0)
        assert r1["bubble_pct"] == pytest.approx(10.0)
        assert r1["host_gap_pct"] == pytest.approx(20.0)
        # the four components cover the wall exactly
        for r in rows:
            assert (r["compute_pct"] + r["collective_exposed_pct"]
                    + r["bubble_pct"] + r["host_gap_pct"]) == \
                pytest.approx(100.0)

    def test_report_anatomy_text_table(self, tmp_path, capsys):
        stream, logdir = _anatomy_fixture(tmp_path)
        rc = monitor_report.main(["report", stream, "--anatomy",
                                  "--trace", logdir])
        assert rc == 0
        out = capsys.readouterr().out
        assert "step anatomy" in out
        assert "/device:TPU:0" in out

    def test_report_anatomy_missing_trace_exits_2(self, tmp_path, capsys):
        stream, _ = _anatomy_fixture(tmp_path)
        rc = monitor_report.main(["report", stream, "--anatomy",
                                  "--trace", str(tmp_path / "nope")])
        assert rc == 2
        assert "searched" in capsys.readouterr().err


class TestValidateProfileArtifacts:
    """`tools/validate_metrics.py --profile/--costdb` gate the new
    artifacts like bench/gate records."""

    def test_costdb_flag_accepts_and_rejects(self, tmp_path):
        from apex_tpu.prof.calibrate import build_costdb, write_costdb

        tool = _load_validate_tool()
        db = build_costdb([], [], device_kind="TPU v5p", backend="tpu")
        p = tmp_path / "costdb.json"
        write_costdb(str(p), db)
        assert tool.main(["--costdb", str(p)]) == 0
        other = tmp_path / "bench.json"
        other.write_text(json.dumps({"metric": "m", "value": 1.0,
                                     "unit": "u"}))
        assert tool.main(["--costdb", str(other)]) == 1

    def test_pipeline_record_emits_validates_and_reports(self, tmp_path,
                                                         capsys):
        """Schema-drift gate for the ``pipeline`` bench record: freshly
        emitted OK and SKIP forms pass the validator CLI (content AND
        ``--pipeline`` forced dispatch), a hand-forged nan fails, a
        reason-free SKIP fails, and ``monitor report`` renders the
        pipeline-bench line from the same stream."""
        tool = _load_validate_tool()
        path = tmp_path / "events.jsonl"
        monitor.enable(str(path))
        try:
            monitor.emit_pipeline(
                "OK", schedule="zb", pipeline_size=4, virtual_chunks=1,
                num_microbatches=8, overlap_p2p=False,
                tokens_per_s=90000.0, tokens_per_s_1f1b=82000.0,
                vs_1f1b=1.0976, bubble_pct=14.2, bubble_pct_1f1b=24.8,
                bubble_pct_geometry=20.0, bubble_pct_1f1b_geometry=27.27,
                p2p_bytes_per_step=1 << 20, jit_cache_ok=True)
            monitor.emit_pipeline(
                "SKIP", reason="no TPU attached", schedule="zb",
                bubble_pct=("skipped", "no device trace"),
                bubble_pct_geometry=20.0)
        finally:
            monitor.disable()
        assert tool.main([str(path)]) == 0
        assert tool.main(["--pipeline", str(path)]) == 0

        pipes = [r for r in (json.loads(ln)
                             for ln in path.read_text().splitlines())
                 if r.get("kind") == "pipeline"]
        bad = dict(pipes[0])
        bad["tokens_per_s"] = "nan"
        bad_path = tmp_path / "bad.jsonl"
        bad_path.write_text(json.dumps(bad) + "\n")
        assert tool.main([str(bad_path)]) == 1
        noreason = dict(pipes[1])
        del noreason["reason"]
        nr_path = tmp_path / "nr.jsonl"
        nr_path.write_text(json.dumps(noreason) + "\n")
        assert tool.main([str(nr_path)]) == 1
        # a stream without any pipeline record fails the forced dispatch
        bare = tmp_path / "bare.jsonl"
        monitor.enable(str(bare))
        try:
            monitor.emit_event("x")
        finally:
            monitor.disable()
        assert tool.main(["--pipeline", str(bare)]) == 1

        assert monitor_report.main(["report", str(path)]) == 0
        out = capsys.readouterr().out
        assert "pipeline-bench" in out and "SKIP(no TPU attached)" in out
        summary = monitor_report.aggregate(
            monitor_report.read_records(open(path)))
        assert summary["pipeline_bench"]["status"] == "SKIP"

    def test_profile_flag_requires_profile_record(self, tmp_path):
        tool = _load_validate_tool()
        path = tmp_path / "events.jsonl"
        monitor.enable(str(path))
        try:
            monitor.emit_profile("SKIP", reason="host-only trace")
        finally:
            monitor.disable()
        assert tool.main(["--profile", str(path)]) == 0
        bare = tmp_path / "bare.jsonl"
        monitor.enable(str(bare))
        try:
            monitor.emit_event("x")
        finally:
            monitor.disable()
        assert tool.main(["--profile", str(bare)]) == 1


class TestPipelineBenchLeg:
    def test_bench_pipeline_emits_valid_skip_record_off_tpu(
            self, tmp_path, monkeypatch, capsys):
        """The pipeline-schedule leg end-to-end at smoke scale,
        in-process: off-TPU the record must be an explicit SKIP —
        schema-valid, no nan — carrying both schedules' smoke tokens/s,
        the geometry bubbles with zb < 1f1b, skip-objects for the
        measured bubbles, and the recompile-free witness."""
        import importlib.util

        monkeypatch.delenv("APEX_TPU_MONITOR", raising=False)
        root = os.path.join(os.path.dirname(__file__), "..")
        spec = importlib.util.spec_from_file_location(
            "bench_pipeline_leg", os.path.join(root, "bench.py"))
        bench = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bench)
        try:
            bench.pipeline_main()
        finally:
            monitor.disable()
        record = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert record["kind"] == "pipeline"
        assert record["status"] == "SKIP" and record["reason"]
        assert record["schedule"] == "zb"
        assert record["tokens_per_s"] > 0
        assert record["tokens_per_s_1f1b"] > 0
        assert record["bubble_pct"]["skipped"] is True
        assert (record["bubble_pct_geometry"]
                < record["bubble_pct_1f1b_geometry"])
        assert record["jit_cache_ok"] is True
        assert monitor.validate(record) == []


class TestProfileBenchLeg:
    def test_bench_profile_emits_valid_skip_record_off_tpu(
            self, tmp_path, monkeypatch, capsys):
        """The step-anatomy leg end-to-end at smoke scale, in-process
        (the subprocess import tax would blow the tier-1 budget): off-TPU
        the trace is host-only, so the record must be an explicit SKIP —
        schema-valid, no nan — with the costdb and merged timeline
        artifacts written and validator-clean."""
        import importlib.util

        monkeypatch.delenv("APEX_TPU_MONITOR", raising=False)
        root = os.path.join(os.path.dirname(__file__), "..")
        spec = importlib.util.spec_from_file_location(
            "bench_profile_leg", os.path.join(root, "bench.py"))
        bench = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bench)
        logdir = str(tmp_path / "prof")
        try:
            bench.profile_main(["--logdir", logdir])
        finally:
            monitor.disable()
        record = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert record["kind"] == "profile"
        assert record["status"] == "SKIP" and record["reason"]
        assert record["steps"] >= 1
        assert record["step_wall_ms"] > 0
        assert record["compute_pct"]["skipped"] is True
        assert monitor.validate(record) == []
        assert os.path.exists(record["costdb_path"])
        assert os.path.exists(record["timeline_path"])
        tool = _load_validate_tool()
        assert tool.main(["--costdb", record["costdb_path"]]) == 0
        assert tool.main([os.path.join(logdir, "events.jsonl")]) == 0
