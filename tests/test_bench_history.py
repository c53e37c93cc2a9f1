"""tools/bench_history.py — the trajectory regression gate (ISSUE 10
satellite): tolerance-bounded tokens/s comparison against the
``BENCH_r*.json`` artifacts of a history directory, one-line verdicts,
SKIP-record honesty, and the off-TPU schema-only smoke.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import bench_history  # noqa: E402

def _hist(tmp_path, rounds):
    """Write BENCH_r<N>.json driver envelopes into tmp_path."""
    for n, (value, spread) in enumerate(rounds, 1):
        payload = {"parsed": {"metric": "m_tok", "value": value,
                              "unit": "tokens/s/chip",
                              "spread_pct": spread}}
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(
            json.dumps(payload))


def _fresh(tmp_path, value, spread=0.1, name="fresh.json"):
    p = tmp_path / name
    p.write_text(json.dumps({"metric": "m_tok", "value": value,
                             "unit": "tokens/s/chip",
                             "spread_pct": spread}))
    return str(p)


class TestGate:
    def test_in_tolerance_passes(self, tmp_path, capsys):
        _hist(tmp_path, [(100.0, 0.5), (110.0, 0.5)])
        rc = bench_history.main([_fresh(tmp_path, 108.0),
                                 "--root", str(tmp_path)])
        assert rc == 0
        assert "OK m_tok" in capsys.readouterr().out

    def test_regression_fails_with_one_line_diff(self, tmp_path, capsys):
        _hist(tmp_path, [(100.0, 0.5), (110.0, 0.5)])
        rc = bench_history.main([_fresh(tmp_path, 90.0),
                                 "--root", str(tmp_path)])
        assert rc == 1
        out = capsys.readouterr().out.strip()
        assert out.count("\n") == 0  # ONE line
        assert out.startswith("REGRESSION m_tok")
        assert "BENCH_r02.json" in out and "-18.18%" in out

    def test_compares_latest_not_best(self, tmp_path):
        """The trajectory's newest point is the reference — an old
        outlier round must not move the bar."""
        _hist(tmp_path, [(140.0, 0.5), (110.0, 0.5)])
        assert bench_history.main([_fresh(tmp_path, 108.0),
                                   "--root", str(tmp_path)]) == 0

    def test_spread_widens_the_band(self, tmp_path):
        _hist(tmp_path, [(110.0, 4.0)])  # noisy history
        # 8% down: outside tol 3% alone, inside 3 + 4 + 2
        assert bench_history.main([_fresh(tmp_path, 101.2, spread=2.0),
                                   "--root", str(tmp_path)]) == 0
        assert bench_history.main([_fresh(tmp_path, 99.0, spread=0.0),
                                   "--root", str(tmp_path),
                                   "--tolerance-pct", "1"]) == 1

    def test_round_ordering_is_numeric(self, tmp_path):
        """r10 is newer than r9 (lexicographic sort would invert)."""
        for n, v in [(9, 100.0), (10, 200.0)]:
            (tmp_path / f"BENCH_r{n}.json").write_text(json.dumps(
                {"parsed": {"metric": "m_tok", "value": v,
                            "unit": "u", "spread_pct": 0.0}}))
        assert bench_history.main([_fresh(tmp_path, 100.0),
                                   "--root", str(tmp_path)]) == 1

    def test_serve_record_and_skip_honesty(self, tmp_path, capsys):
        """Monitor records gate too — and a SKIP record claims nothing,
        so it can never regress."""
        hist = tmp_path / "BENCH_r01.json"
        hist.write_text(json.dumps(
            {"kind": "serve", "schema": 1, "status": "OK",
             "tokens_per_s": 5000.0}))
        fresh = tmp_path / "serve.json"
        fresh.write_text(json.dumps(
            {"kind": "serve", "schema": 1, "status": "OK",
             "tokens_per_s": 3000.0}))
        rc = bench_history.main([str(fresh), "--root", str(tmp_path)])
        assert rc == 1
        assert "serve_tokens_per_s" in capsys.readouterr().out
        skip = tmp_path / "skip.json"
        skip.write_text(json.dumps(
            {"kind": "serve", "schema": 1, "status": "SKIP",
             "reason": "no TPU", "tokens_per_s": 1.0}))
        rc = bench_history.main([str(skip), "--root", str(tmp_path)])
        assert rc == 0
        assert "SKIP" in capsys.readouterr().out

    def test_jsonl_stream_uses_last_record(self, tmp_path):
        _hist(tmp_path, [(100.0, 0.0)])
        stream = tmp_path / "run.jsonl"
        stream.write_text(
            json.dumps({"kind": "meta", "schema": 1}) + "\n"
            + json.dumps({"metric": "m_tok", "value": 99.0,
                          "unit": "u"}) + "\n")
        assert bench_history.main([str(stream),
                                   "--root", str(tmp_path)]) == 0

    def test_no_matching_history_is_skip(self, tmp_path, capsys):
        _hist(tmp_path, [(100.0, 0.0)])
        fresh = tmp_path / "other.json"
        fresh.write_text(json.dumps({"metric": "other", "value": 1.0,
                                     "unit": "u"}))
        assert bench_history.main([str(fresh),
                                   "--root", str(tmp_path)]) == 0
        assert "SKIP" in capsys.readouterr().out

    def test_unreadable_fresh_is_usage_error(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        assert bench_history.main([str(bad),
                                   "--root", str(tmp_path)]) == 2


class TestTier1Smoke:
    def test_schema_only_over_a_history(self, tmp_path, capsys):
        """The off-TPU tier-1 smoke the ISSUE wires in: the gate's
        plumbing (extraction + shared monitor schema) validates a
        BENCH_r*.json trajectory, no throughput claim involved."""
        _hist(tmp_path, [(100.0, 0.5), (110.0, 0.5)])
        fresh = _fresh(tmp_path, 1.0)
        rc = bench_history.main(["--schema-only", fresh,
                                 "--root", str(tmp_path)])
        assert rc == 0
        assert "SCHEMA-ONLY OK" in capsys.readouterr().out

    def test_history_extracts_a_trajectory(self, tmp_path):
        _hist(tmp_path, [(100.0, 0.5), (110.0, 0.5), (112.0, 0.1),
                         (111.0, 0.2)])
        rows = bench_history.collect_history("BENCH_r*.json", str(tmp_path))
        assert len(rows) == 4
        assert {m for _, m, _, _ in rows} == {"m_tok"}
        assert [v for _, _, v, _ in rows] == [100.0, 110.0, 112.0, 111.0]

    def test_schema_only_catches_a_broken_artifact(self, tmp_path):
        bad = tmp_path / "fresh.json"
        bad.write_text(json.dumps({"metric": "m", "unit": "u"}))  # no value
        assert bench_history.main(["--schema-only", str(bad),
                                   "--root", str(tmp_path)]) == 2

    def test_schema_only_truncated_history_is_exit_2_not_traceback(
            self, tmp_path, capsys):
        """A killed run's half-written artifact must produce one
        diagnostic line and exit 2, never a traceback (review
        finding: CI keys on exit 2 = broken artifact)."""
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps({"metric": "m_tok", "value": 1.0,
                                     "unit": "u"}))
        (tmp_path / "BENCH_r01.json").write_text('{"parsed": {"met')
        rc = bench_history.main(["--schema-only", str(fresh),
                                 "--root", str(tmp_path)])
        assert rc == 2
        assert "unreadable" in capsys.readouterr().err

    def test_jsonl_stream_prefers_last_claim_record(self, tmp_path):
        """A telemetry stream trailing with windows/meta after the
        serve record still extracts the claim record."""
        _hist(tmp_path, [(100.0, 0.0)])
        stream = tmp_path / "run.jsonl"
        stream.write_text(
            json.dumps({"metric": "m_tok", "value": 99.5,
                        "unit": "u"}) + "\n"
            + json.dumps({"kind": "meta", "schema": 1}) + "\n"
            + json.dumps({"kind": "serve_window", "schema": 1,
                          "status": "SKIP", "reason": "x",
                          "window_s": 0.5}) + "\n")
        assert bench_history.main([str(stream),
                                   "--root", str(tmp_path)]) == 0


class TestPrefixHitLatencySeries:
    """ISSUE 13 satellite: an OK serve record's prefix_hit_ttft_p50_ms
    gates as a LOWER-is-better series next to its throughput."""

    def _serve(self, tok, hit_ms=None, status="OK"):
        rec = {"kind": "serve", "schema": 1, "status": status,
               "tokens_per_s": tok}
        if status == "SKIP":
            rec["reason"] = "no TPU"
        if hit_ms is not None:
            rec["prefix_hit_ttft_p50_ms"] = hit_ms
        return rec

    def test_extract_all_carries_both_series(self):
        rows = bench_history.extract_all(self._serve(5000.0, 12.0))
        assert ("serve_tokens_per_s", 5000.0, 0.0) in rows
        assert ("serve_prefix_hit_ttft_p50_ms", 12.0, 0.0) in rows
        # pre-tier-2 records (no hit field) carry throughput only
        assert bench_history.extract_all(self._serve(5000.0)) == [
            ("serve_tokens_per_s", 5000.0, 0.0)]
        # a skip OBJECT (no hit landed) is not a number: not gated
        rec = self._serve(5000.0)
        rec["prefix_hit_ttft_p50_ms"] = {"skipped": True,
                                         "reason": "no hits"}
        assert len(bench_history.extract_all(rec)) == 1
        # extract() still returns the PRIMARY claim
        assert bench_history.extract(self._serve(5000.0, 12.0))[0] == \
            "serve_tokens_per_s"

    def test_hit_ttft_drift_up_is_a_regression(self, tmp_path, capsys):
        (tmp_path / "BENCH_r01.json").write_text(
            json.dumps(self._serve(5000.0, 10.0)))
        fresh = tmp_path / "fresh.json"
        # throughput holds, hit TTFT +50%: lower-is-better fails
        fresh.write_text(json.dumps(self._serve(5000.0, 15.0)))
        rc = bench_history.main([str(fresh), "--root", str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "OK serve_tokens_per_s" in out
        assert "REGRESSION serve_prefix_hit_ttft_p50_ms" in out
        # faster hits (drift DOWN) are an improvement, not a regression
        fresh.write_text(json.dumps(self._serve(5000.0, 7.0)))
        rc = bench_history.main([str(fresh), "--root", str(tmp_path)])
        assert rc == 0
        assert "OK serve_prefix_hit_ttft_p50_ms" in \
            capsys.readouterr().out

    def test_throughput_regression_still_gates_with_both(self, tmp_path,
                                                         capsys):
        (tmp_path / "BENCH_r01.json").write_text(
            json.dumps(self._serve(5000.0, 10.0)))
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps(self._serve(3000.0, 10.0)))
        rc = bench_history.main([str(fresh), "--root", str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "REGRESSION serve_tokens_per_s" in out
        assert "OK serve_prefix_hit_ttft_p50_ms" in out

    def test_skip_record_still_claims_nothing(self, tmp_path, capsys):
        (tmp_path / "BENCH_r01.json").write_text(
            json.dumps(self._serve(5000.0, 10.0)))
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps(
            self._serve(1.0, 99999.0, status="SKIP")))
        assert bench_history.main([str(fresh),
                                   "--root", str(tmp_path)]) == 0
        assert "SKIP" in capsys.readouterr().out

    def test_no_hit_history_is_skip_for_that_series_only(self, tmp_path,
                                                         capsys):
        """Fresh record carries the new series but the trajectory
        predates it: the latency series SKIPs, throughput still
        gates."""
        (tmp_path / "BENCH_r01.json").write_text(
            json.dumps(self._serve(5000.0)))  # pre-tier-2 history
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps(self._serve(4950.0, 12.0)))
        rc = bench_history.main([str(fresh), "--root", str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "OK serve_tokens_per_s" in out
        assert "SKIP: no history artifact carries metric " \
            "'serve_prefix_hit_ttft_p50_ms'" in out


class TestSpecSeries:
    """ISSUE 15 satellite: an OK spec record gates its per-request
    throughput (higher-is-better) AND its acceptance rate as a tracked
    series; pre-spec history artifacts SKIP the new series only."""

    def _spec(self, tps, rate=None, status="OK", spread=0.0):
        rec = {"kind": "spec", "schema": 1, "status": status,
               "tokens_per_s_request": tps, "spread_pct": spread}
        if status == "SKIP":
            rec["reason"] = "no TPU"
        if rate is not None:
            rec["acceptance_rate"] = rate
        return rec

    def test_extract_all_carries_both_series(self):
        rows = bench_history.extract_all(self._spec(900.0, 0.8))
        assert ("spec_tokens_per_s_request", 900.0, 0.0) in rows
        assert ("spec_acceptance_rate", 0.8, 0.0) in rows
        # the per-request throughput is the PRIMARY claim
        assert bench_history.extract(self._spec(900.0, 0.8))[0] == \
            "spec_tokens_per_s_request"
        # a rate that rode as a skip object is not gated
        rec = self._spec(900.0)
        rec["acceptance_rate"] = {"skipped": True, "reason": "no rounds"}
        assert bench_history.extract_all(rec) == [
            ("spec_tokens_per_s_request", 900.0, 0.0)]

    def test_ok_record_without_throughput_is_an_error(self):
        with pytest.raises(ValueError, match="tokens_per_s_request"):
            bench_history.extract_all(
                {"kind": "spec", "schema": 1, "status": "OK"})

    def test_throughput_regression_fails(self, tmp_path, capsys):
        (tmp_path / "BENCH_r01.json").write_text(
            json.dumps(self._spec(1000.0, 0.8)))
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps(self._spec(800.0, 0.8)))
        rc = bench_history.main([str(fresh), "--root", str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "REGRESSION spec_tokens_per_s_request" in out
        assert "OK spec_acceptance_rate" in out

    def test_acceptance_collapse_fails(self, tmp_path, capsys):
        (tmp_path / "BENCH_r01.json").write_text(
            json.dumps(self._spec(1000.0, 0.8)))
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps(self._spec(1000.0, 0.4)))
        rc = bench_history.main([str(fresh), "--root", str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "OK spec_tokens_per_s_request" in out
        assert "REGRESSION spec_acceptance_rate" in out

    def test_skip_record_claims_nothing(self, tmp_path, capsys):
        (tmp_path / "BENCH_r01.json").write_text(
            json.dumps(self._spec(1000.0, 0.8)))
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps(self._spec(1.0, 0.01, status="SKIP")))
        assert bench_history.main([str(fresh),
                                   "--root", str(tmp_path)]) == 0
        assert "SKIP" in capsys.readouterr().out

    def test_pre_spec_history_skips_the_new_series_only(self, tmp_path,
                                                        capsys):
        """The REAL upgrade path: the checked-in trajectory predates
        the spec leg entirely — a fresh OK spec record must SKIP both
        of its series (exit 0), while a flagship artifact in the same
        history still gates its own metric (regression-tested: the
        pre-spec artifacts are untouched, only the spec series are
        absent)."""
        _hist(tmp_path, [(100.0, 0.5)])  # pre-spec flagship history
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps(self._spec(900.0, 0.8)))
        rc = bench_history.main([str(fresh), "--root", str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "SKIP: no history artifact carries metric " \
            "'spec_tokens_per_s_request'" in out
        assert "SKIP: no history artifact carries metric " \
            "'spec_acceptance_rate'" in out
        # the flagship series still gates against the same history
        assert bench_history.main([_fresh(tmp_path, 90.0),
                                   "--root", str(tmp_path)]) == 1
