"""Aggregate a monitor JSONL stream into a step-timeline summary.

``python -m apex_tpu.monitor report events.jsonl`` prints a human summary
(tokens/s, derived MFU, overflow rate, pipeline bubble %, collective
volume); ``--json`` prints one machine-readable JSON object instead.
``report --attribution`` decomposes each served request's e2e latency
into queue/prefill/decode/spec/preempt/swap components (the
``serve_attribution`` record); ``python -m apex_tpu.monitor trace``
exports the stream as Chrome trace-event JSON (one track per rank, one
per request — chrome://tracing / Perfetto). A requested section whose
records are absent from the stream prints an explicit ``SKIP(reason)``
line, never a silent empty section.

The MFU convention is the same spec-peak one the bench artifact uses:
analytic model FLOPs per token (from the ``meta``
record) × achieved tokens/s ÷ the chip's public peak dense bf16 FLOP/s
(:data:`PEAK_FLOPS_BY_DEVICE`, which ``bench.py`` imports — one table, one
code path). The headline tokens/s uses the **best** (minimum-duration)
step, matching the bench's min-of-passes headline; the mean is reported
alongside.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, Iterable, List, Optional

# peak dense bf16 FLOP/s per chip by device kind (public spec sheets) —
# THE spec-peak table: bench.py and the report both read it, so "mfu" means
# the same thing in a bench result and in `monitor report` output.
PEAK_FLOPS_BY_DEVICE = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def spec_peak_flops(device_kind: Optional[str]) -> Optional[float]:
    """Peak dense bf16 FLOP/s for a device kind, or None when unknown
    (CPU hosts, future chips) — callers must then omit MFU rather than
    fabricate it."""
    if device_kind is None:
        return None
    return PEAK_FLOPS_BY_DEVICE.get(device_kind)


def read_records(lines: Iterable[str]) -> List[Dict[str, Any]]:
    records = []
    for line in lines:
        line = line.strip()
        if line:
            records.append(json.loads(line))
    return records


def aggregate(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Fold a record stream into the step-timeline summary dict.

    A file holding several runs (appended streams; each run opens with a
    ``meta`` record) aggregates the LAST run only — a stale run's faster
    steps must not leak into this run's tokens/s headline. The summary
    carries ``runs_in_file`` when earlier runs were skipped.
    """
    meta_idx = [i for i, r in enumerate(records) if r.get("kind") == "meta"]
    runs_in_file = len(meta_idx)
    if runs_in_file > 1:
        records = records[meta_idx[-1]:]
    meta: Dict[str, Any] = {}
    steps = []
    gate_records = []
    decode_records = []
    longseq_records = []
    tp_overlap_records = []
    serve_records = []
    serve_window_records = []
    pipeline_records = []
    plan_records = []
    ckpt_records = []
    spec_records = []
    tp_serve_records = []
    schedule = None
    for rec in records:
        kind = rec.get("kind")
        if kind == "meta":
            meta.update({k: v for k, v in rec.items()
                         if k not in ("schema", "kind", "t_s", "process",
                                      "rank")})
        elif kind == "step":
            steps.append(rec)
        elif kind == "gate":
            gate_records.append(rec)
        elif kind == "decode":
            decode_records.append(rec)
        elif kind == "longseq_bias":
            longseq_records.append(rec)
        elif kind == "tp_overlap":
            tp_overlap_records.append(rec)
        elif kind == "serve":
            serve_records.append(rec)
        elif kind == "serve_window":
            serve_window_records.append(rec)
        elif kind == "pipeline":
            pipeline_records.append(rec)
        elif kind == "plan":
            plan_records.append(rec)
        elif kind == "ckpt":
            ckpt_records.append(rec)
        elif kind == "spec":
            spec_records.append(rec)
        elif kind == "tp_serve":
            tp_serve_records.append(rec)
        elif kind == "event" and rec.get("name") == "pipeline_schedule":
            schedule = rec

    summary: Dict[str, Any] = {
        "num_steps": len(steps),
        "num_records": len(records),
    }
    if runs_in_file > 1:
        summary["runs_in_file"] = runs_in_file
    if meta:
        summary["meta"] = meta

    durs = [s["dur_s"] for s in steps
            if isinstance(s.get("dur_s"), (int, float)) and s["dur_s"] > 0]
    if durs:
        summary["step_time_s"] = {
            "best": min(durs),
            "mean": sum(durs) / len(durs),
            "worst": max(durs),
        }
    token_steps = [s for s in steps
                   if isinstance(s.get("tokens"), (int, float))
                   and isinstance(s.get("dur_s"), (int, float))
                   and s["dur_s"] > 0]
    if token_steps:
        best = min(token_steps, key=lambda s: s["dur_s"] / s["tokens"])
        total_tokens = sum(s["tokens"] for s in token_steps)
        total_time = sum(s["dur_s"] for s in token_steps)
        summary["tokens_per_s"] = {
            "best": best["tokens"] / best["dur_s"],
            "mean": total_tokens / total_time,
        }
        fpt = meta.get("model_flops_per_token")
        peak = spec_peak_flops(meta.get("device_kind"))
        if isinstance(fpt, (int, float)):
            flops_per_s = fpt * summary["tokens_per_s"]["best"]
            summary["model_tflops"] = flops_per_s / 1e12
            if peak:
                summary["mfu"] = flops_per_s / peak

    # overflow rate: per-step overflow counters, falling back to the
    # lifetime gauge delta across the stream
    overflows = sum(s.get("counters", {}).get("amp/overflow_steps", 0)
                    for s in steps)
    if not overflows and steps:
        totals = [s["gauges"].get("amp/skipped_steps_total")
                  for s in steps
                  if "amp/skipped_steps_total" in s.get("gauges", {})]
        if len(totals) >= 2:
            overflows = totals[-1] - totals[0]
    if steps:
        summary["overflow_rate"] = overflows / len(steps)
    scales = [s["gauges"].get("amp/loss_scale") for s in steps
              if "amp/loss_scale" in s.get("gauges", {})]
    if scales:
        summary["loss_scale_last"] = scales[-1]

    if schedule is not None:
        summary["pipeline"] = {
            "bubble_fraction": schedule.get("bubble_fraction"),
            "num_microbatches": schedule.get("num_microbatches"),
            "pipeline_size": schedule.get("pipeline_size"),
            "virtual_chunks": schedule.get("virtual_chunks"),
            "ticks": schedule.get("ticks"),
            "schedule": schedule.get("schedule"),
            "overlap_p2p": schedule.get("overlap_p2p"),
            "bubble_fraction_step": schedule.get("bubble_fraction_step"),
        }
        # per-(microbatch, stage) wall time: a chunk-tick is exactly one
        # microbatch through one (virtual) stage, so when the caller timed
        # the schedule call (monitor.timer("pipeline/fwd_bwd") around the
        # blocking fwd/bwd), total time / calls / ticks is the per-tick
        # wall estimate (forward-sweep convention; backward ticks ride in
        # the same timed window, so this upper-bounds the forward tick)
        ticks = schedule.get("ticks")
        tot_n, tot_s = 0, 0.0
        for s in steps:
            t = s.get("timers", {}).get("pipeline/fwd_bwd")
            if t:
                tot_n += t.get("count", 0)
                tot_s += t.get("total_s", 0.0)
        if ticks and tot_n:
            summary["pipeline"]["per_tick_wall_s"] = tot_s / tot_n / ticks

    # collective volume from the LAST step's lifetime totals: trace-time
    # counting runs during warm-up compilation, usually BEFORE step 0's
    # delta baseline, so summing per-step deltas would read 0. Totals are
    # per traced program (re-traces add to them), not per executed step.
    collectives: Dict[str, Dict[str, float]] = {}
    totals = steps[-1].get("counters_total", {}) if steps else {}
    if not totals:  # pre-counters_total streams: fall back to delta sums
        for s in steps:
            for name, v in s.get("counters", {}).items():
                if name.startswith("collective/"):
                    totals[name] = totals.get(name, 0) + v
    for name, v in totals.items():
        if name.startswith("collective/"):
            base, sep, field = name[len("collective/"):].rpartition("_")
            if not sep:  # a stray unsuffixed counter must not kill the CLI
                base, field = field, "calls"
            collectives.setdefault(base, {})[field] = v
    if collectives:
        summary["collectives"] = collectives

    def status_summary(recs, fields):
        # a status-carrying bench record (decode / longseq_bias): last
        # record wins (same one-run-per-stream rule the step headline
        # follows); explicit skip objects surface as a skipped-metric
        # list, mirroring the gate summary
        d = recs[-1]
        return {
            "status": d.get("status"),
            "skipped": sorted(k for k, v in d.items()
                              if isinstance(v, dict) and v.get("skipped")),
            **{k: d[k] for k in (*fields, "reason")
               if isinstance(d.get(k), (int, float, str))},
        }

    if decode_records:
        summary["decode"] = status_summary(
            decode_records, ("tokens_per_s", "prefill_ms", "spread_pct",
                             "vs_naive", "batch", "prompt_len",
                             "new_tokens"))

    if longseq_records:
        summary["longseq_bias"] = status_summary(
            longseq_records, ("tokens_per_s", "tokens_per_s_materialized",
                              "vs_materialized", "hbm_peak_mb",
                              "hbm_peak_materialized_mb", "seq"))

    if tp_overlap_records:
        summary["tp_overlap"] = status_summary(
            tp_overlap_records, ("tokens_per_s", "tokens_per_s_blocking",
                                 "vs_blocking", "tp", "batch", "seq",
                                 "spread_pct", "spread_pct_blocking"))

    if serve_records:
        summary["serve"] = status_summary(
            serve_records, ("tokens_per_s", "latency_p50_ms",
                            "latency_p99_ms", "ttft_p50_ms", "ttft_p99_ms",
                            "prefix_hit_rate", "prefix_hit_ttft_p50_ms",
                            "prefix_miss_ttft_p50_ms", "preemptions",
                            "recompute_tokens", "blocks_resident",
                            "churn_parity",
                            "occupancy_pct", "vs_single_request",
                            "requests", "slots", "block_size",
                            "blocks_high_water",
                            "admission_blocked_slots",
                            "admission_blocked_blocks", "queue_peak",
                            "serve_windows", "telemetry_overhead_pct"))
        anomaly = serve_records[-1].get("serve_anomaly")
        if isinstance(anomaly, dict):
            summary["serve"]["serve_anomaly"] = anomaly

    if serve_window_records:
        # the live-SLO window trail: count + the LAST window's view
        # (the full trail is the --serve-timeline rendering's job)
        last = serve_window_records[-1]
        summary["serve_window"] = {
            "windows": len(serve_window_records),
            **{k: last[k] for k in
               ("status", "tokens_per_s", "latency_p50_ms",
                "latency_p99_ms", "ttft_p50_ms", "queue_depth",
                "occupancy_pct", "blocks_high_water")
               if isinstance(last.get(k), (int, float, str))},
        }
        anomaly = last.get("serve_anomaly")
        if isinstance(anomaly, dict):
            summary["serve_window"]["serve_anomaly"] = anomaly

    if pipeline_records:
        summary["pipeline_bench"] = status_summary(
            pipeline_records, ("schedule", "tokens_per_s",
                               "tokens_per_s_1f1b", "vs_1f1b",
                               "bubble_pct", "bubble_pct_1f1b",
                               "bubble_pct_geometry",
                               "bubble_pct_1f1b_geometry",
                               "pipeline_size", "virtual_chunks",
                               "num_microbatches", "p2p_bytes_per_step"))

    if plan_records:
        summary["plan"] = status_summary(
            plan_records, ("chosen_describe", "predicted_step_ms",
                           "measured_step_ms",
                           "predicted_vs_measured_err_pct",
                           "confidence", "chips", "searched", "feasible",
                           "costdb_source"))
        uncal = plan_records[-1].get("uncalibrated")
        if isinstance(uncal, list):
            summary["plan"]["uncalibrated"] = uncal

    if ckpt_records:
        summary["ckpt"] = status_summary(
            ckpt_records, ("save_overhead_pct", "step_ms",
                           "step_ms_saving", "snapshot_ms", "write_ms",
                           "restore_ms", "bytes_written", "steps",
                           "saves", "save_every", "dp", "async_save",
                           "bitwise_resume_ok", "elastic_resume_ok"))

    if spec_records:
        summary["spec"] = status_summary(
            spec_records, ("tokens_per_s_request",
                           "baseline_tokens_per_s_request", "speedup",
                           "tokens_per_s_churn", "speedup_churn",
                           "acceptance_rate", "accepted_per_round",
                           "rounds", "draft_k", "drafter", "kv_dtype",
                           "kv_quant_logit_err", "greedy_parity",
                           "churn_parity", "jit_cache_ok",
                           "spread_pct"))

    if tp_serve_records:
        summary["tp_serve"] = status_summary(
            tp_serve_records, ("tp", "tokens_per_s",
                               "baseline_tokens_per_s",
                               "ttft_ms_prefill_role",
                               "ttft_ms_monolithic", "handoff_blocks",
                               "handoff_transfer_bytes",
                               "handoff_transfer_ms",
                               "digests_verified",
                               "collective_ppermute_calls",
                               "collective_ppermute_bytes",
                               "decode_steps",
                               "collective_bytes_per_step",
                               "greedy_parity", "handoff_parity",
                               "jit_cache_ok", "kv_dtype", "requests",
                               "num_blocks", "pool_mb_per_shard",
                               "pool_mb_total", "spread_pct"))

    if gate_records:
        summary["gates"] = [
            {"name": g.get("name"), "ok": g.get("ok"),
             "skipped": sorted(k for k, v in g.get("metrics", {}).items()
                               if isinstance(v, dict) and v.get("skipped"))}
            for g in gate_records
        ]
    return summary


def _anomaly_flags(anom: Dict[str, Any]) -> List[str]:
    """Human-readable flags from a ``serve_anomaly`` section (empty
    when the run was clean)."""
    flags = []
    if anom.get("straggler_steps"):
        flags.append(f"straggler x{anom['straggler_steps']}"
                     + (f" (last {anom['straggler_last_ratio']:g}x median)"
                        if isinstance(anom.get("straggler_last_ratio"),
                                      (int, float))
                        and anom["straggler_last_ratio"] else ""))
    if anom.get("queue_buildup"):
        flags.append("queue buildup")
    if anom.get("slo_burn"):
        flags.append(f"SLO burn ({anom.get('ttft_over_slo', '?')} "
                     f"first tokens over threshold)")
    if anom.get("leaked_blocks"):
        flags.append(f"LEAK {anom['leaked_blocks']} blocks")
    return flags


# --- the request-lifecycle timeline (`report --serve-timeline`) --------------

def serve_timeline(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Fold ``serve_event``/``serve_window`` records into the
    per-request lifecycle view: one row per request (queue wait, chunk
    count, prefill/TTFT/decode durations, blocks held, finish) plus the
    window trail. Rows are dicts so ``--json`` can carry them.

    Appended multi-run streams fold the LAST run only (the same
    run-splitting-at-``meta`` rule :func:`aggregate` applies) — rids
    restart at 0 per run, so folding across runs would cross-wire two
    runs' lifecycles into one garbage row."""
    meta_idx = [i for i, r in enumerate(records)
                if r.get("kind") == "meta"]
    if len(meta_idx) > 1:
        records = records[meta_idx[-1]:]
    per_rid: Dict[int, Dict[str, Any]] = {}
    stragglers = []
    swaps = []
    replans = []
    for rec in records:
        if rec.get("kind") != "serve_event":
            continue
        rid = rec.get("rid")
        if rid == -1:  # engine-level events (stragglers, swaps, replans)
            if rec.get("straggler"):
                stragglers.append({k: rec.get(k) for k in
                                   ("at_s", "step", "dur_ms",
                                    "ratio_to_median")})
            elif rec.get("phase") == "swap":
                swaps.append({k: rec.get(k) for k in
                              ("at_s", "step", "swap_source")})
            elif rec.get("phase") == "replan":
                replans.append({k: rec.get(k) for k in
                                ("at_s", "step", "plan_from", "plan_to",
                                 "replan_trigger", "live_knobs",
                                 "deferred_knobs")})
            continue
        row = per_rid.setdefault(rid, {"rid": rid})
        phase = rec.get("phase")
        if phase == "submit":
            row["submit_s"] = rec.get("at_s")
            row["prompt_len"] = rec.get("prompt_len")
            row["max_new_tokens"] = rec.get("max_new_tokens")
        elif phase == "admit":
            row["admit_s"] = rec.get("at_s")
            row["slot"] = rec.get("slot")
            row["queue_wait_ms"] = rec.get("queue_wait_ms")
        elif phase == "prefill_chunk":
            row["chunks"] = rec.get("chunk", 0) + 1
            row["blocks_held"] = rec.get("blocks_held")
        elif phase == "first_token":
            row["ttft_ms"] = rec.get("ttft_ms")
            row["prefill_ms"] = rec.get("prefill_ms")
            row["chunks"] = rec.get("chunks", row.get("chunks"))
            row["blocks_held"] = rec.get("blocks_held")
        elif phase == "evict":
            # preemption, not a terminal transition: the request
            # re-queues for evict-and-recompute and (usually) finishes
            # later — fold the count and the LAST evict's payload in
            row["evictions"] = row.get("evictions", 0) + 1
            row["evict_reason"] = rec.get("evict_reason")
            row["blocks_released"] = rec.get("blocks_released")
            row["requeue_pos"] = rec.get("requeue_pos")
            row["outcome"] = "evicted"  # until a finish overwrites it
        elif phase == "handoff":
            # disaggregated KV streaming: one leg per engine role; a
            # merged two-role stream folds both legs into the row
            # (same rid + trace_id on both sides by construction)
            roles = row.setdefault("handoff_roles", [])
            if rec.get("handoff_role"):
                roles.append(rec["handoff_role"])
            row["handoff_blocks"] = rec.get("blocks")
            row["handoff_bytes"] = (
                row.get("handoff_bytes", 0)
                + (rec.get("transfer_bytes") or 0))
        elif phase == "finish":
            row["finish_s"] = rec.get("at_s")
            row["tokens"] = rec.get("tokens")
            row["decode_ms"] = rec.get("decode_ms")
            row["total_ms"] = rec.get("total_ms")
            row["outcome"] = phase
    requests = sorted(per_rid.values(),
                      key=lambda r: r.get("submit_s") or 0.0)
    windows = [
        {k: rec.get(k) for k in
         ("at_s", "t_s", "window_s", "tokens", "tokens_per_s",
          "latency_p50_ms", "latency_p99_ms", "ttft_p50_ms",
          "queue_depth", "active_slots", "occupancy_pct", "blocks_live",
          "blocks_resident", "prefix_hit_rate", "preemptions",
          "recompute_tokens", "serve_anomaly")}
        for rec in records if rec.get("kind") == "serve_window"
    ]
    return {"requests": requests, "windows": windows,
            "stragglers": stragglers, "swaps": swaps, "replans": replans}


# --- per-request latency attribution (`report --attribution`) ----------------

_ATTRIBUTION_SKIP_REASON = (
    "stream carries no serve_event records — serve with a ServeTelemetry "
    "attached and the monitor enabled")


def serve_attribution_record(records: List[Dict[str, Any]]
                             ) -> Optional[Dict[str, Any]]:
    """The schema-validated ``serve_attribution`` record for a stream:
    per-request e2e latency decomposed into the
    :data:`~apex_tpu.monitor.trace.ATTR_COMPONENTS` partition. Returns
    ``None`` when the stream carries no ``serve_event`` records (the
    caller prints the explicit SKIP line). Appended multi-run streams
    fold the LAST run only (the :func:`serve_timeline` rule — rids
    restart per run). The record's status mirrors the stream's
    ``serve`` record when one is present: a SKIP sweep prices nothing,
    and the report must not promote its numbers."""
    meta_idx = [i for i, r in enumerate(records)
                if r.get("kind") == "meta"]
    if len(meta_idx) > 1:
        records = records[meta_idx[-1]:]
    if not any(r.get("kind") == "serve_event" for r in records):
        return None
    # lazy: the plain report never pays for the trace/registry layers
    from apex_tpu.monitor import registry as registry_lib
    from apex_tpu.monitor import trace as trace_lib
    from apex_tpu.monitor.schema import validate as validate_record

    fields = trace_lib.serve_attribution(records, per_request=True)
    serves = [r for r in records if r.get("kind") == "serve"]
    status = serves[-1].get("status") if serves else None
    reason = serves[-1].get("reason") if serves else None
    if status not in ("OK", "SKIP"):
        status = "SKIP"
        reason = ("attribution computed post-hoc by `monitor report` "
                  "from the lifecycle trail; the stream carries no "
                  "serve record to inherit a measurement status from")
    if status == "SKIP":
        fields.setdefault("reason", reason or "serve record was SKIP")
    record = registry_lib.MetricsRegistry().emit_serve_attribution(
        status, **fields)
    errors = validate_record(record)
    if errors:  # a bug in this module, never a user input problem
        raise ValueError(
            f"serve_attribution record failed validation: {errors}")
    return record


def format_attribution(record: Dict[str, Any]) -> str:
    """Render :func:`serve_attribution_record` as the terminal table:
    one totals line, then one row per finished request showing its
    NONZERO components (every request's components sum to its measured
    e2e latency up to rounding — ``residual`` is the gap)."""
    lines = []
    mr = record.get("max_residual_pct")
    lines.append(
        f"serve attribution: {record.get('requests', 0)} requests"
        + (f", {record['unattributed']} unattributed"
           if record.get("unattributed") else "")
        + f"  components {record.get('components_ms_total', 0.0):.1f} ms"
          f" vs e2e {record.get('e2e_ms_total', 0.0):.1f} ms"
        + (f"  (max residual {mr:.2f}%)"
           if isinstance(mr, (int, float)) else "")
        + (f"  [SKIP({record.get('reason', '?')})]"
           if record.get("status") == "SKIP" else ""))
    comp = record.get("components", {})
    totals = [f"{k[:-3]} {v:.1f}" for k, v in comp.items()
              if isinstance(v, (int, float)) and v > 0]
    if totals:
        lines.append("  totals (ms): " + "  ".join(totals))
    for r in record.get("per_request", []):
        parts = [f"{k[:-3]} {r[k]:.1f}" for k in comp
                 if isinstance(r.get(k), (int, float)) and r[k] > 0]
        lines.append(
            f"  rid {r['rid']:>4}"
            + (f" [{r['trace_id']}]" if r.get("trace_id") else "")
            + f"  e2e {r.get('e2e_ms', 0.0):.1f}ms = "
            + (" + ".join(parts) if parts else "0")
            + (f"  (residual {r['residual_pct']:.2f}%)"
               if isinstance(r.get("residual_pct"), (int, float))
               else "")
            + (f"  [evict x{r['evictions']}]" if r.get("evictions")
               else "")
            + (f"  [{r['spec_rounds']} spec rounds]"
               if r.get("spec_rounds") else ""))
    return "\n".join(lines)


def _ms(v, nd=1) -> str:
    return f"{v:.{nd}f}ms" if isinstance(v, (int, float)) else "-"


def format_serve_timeline(timeline: Dict[str, Any]) -> str:
    """Render :func:`serve_timeline` rows as the terminal table."""
    lines = []
    reqs = timeline["requests"]
    lines.append(f"serve timeline: {len(reqs)} requests, "
                 f"{len(timeline['windows'])} windows, "
                 f"{len(timeline['stragglers'])} straggler steps")
    def _n(r, key):
        # event payload fields land as rec.get(...) and may be None
        v = r.get(key)
        return v if isinstance(v, (int, float)) else "-"

    for r in reqs:
        line = (
            f"  rid {r['rid']:>4}  "
            f"queue {_ms(r.get('queue_wait_ms'))}  "
            f"prefill {_ms(r.get('prefill_ms'))}"
            f"/{_n(r, 'chunks')}ch  "
            f"ttft {_ms(r.get('ttft_ms'))}  "
            f"decode {_ms(r.get('decode_ms'))}"
            f"/{_n(r, 'tokens')}tok  "
            f"blocks {_n(r, 'blocks_held')}  "
            f"{r.get('outcome') or 'in-flight'}")
        if r.get("evictions"):
            # the reserved preemption transition, rendered not dropped:
            # count, reason, blocks released, re-queue position
            line += (f"  [evict x{r['evictions']}: "
                     f"{r.get('evict_reason') or '?'}, "
                     f"{_n(r, 'blocks_released')} blk released, "
                     f"requeued at {_n(r, 'requeue_pos')}]")
        if r.get("handoff_roles"):
            # the disaggregated prefill→decode leg(s) this stream saw
            line += (f"  [handoff {'+'.join(r['handoff_roles'])}: "
                     f"{_n(r, 'handoff_blocks')} blk, "
                     f"{_n(r, 'handoff_bytes')} B]")
        lines.append(line)
    def _num(w, *keys, default="-"):
        # serve_timeline materializes every window key (absent -> None),
        # so dict-get defaults never fire — coalesce None explicitly
        for k in keys:
            v = w.get(k)
            if isinstance(v, (int, float)):
                return v
        return default

    for w in timeline["windows"]:
        anom = w.get("serve_anomaly") or {}
        flags = _anomaly_flags(anom) if isinstance(anom, dict) else []
        tps = w.get("tokens_per_s")
        hr = w.get("prefix_hit_rate")
        # at_s is the serve clock (same base as the request rows);
        # pre-at_s streams fall back to the registry clock
        w_at = _num(w, "at_s", "t_s", default=None)
        lines.append(
            "  window "
            + (f"+{w_at:.2f}s  " if w_at is not None else "")
            + (f"{tps:.1f} tok/s  " if isinstance(tps, (int, float))
               else "")
            + f"p50/p99 {_ms(w.get('latency_p50_ms'), 2)}/"
              f"{_ms(w.get('latency_p99_ms'), 2)}  "
            + f"queue {_num(w, 'queue_depth')}  "
            + f"occ {_num(w, 'occupancy_pct')}%"
            + (f"  hit {100.0 * hr:.0f}%"
               if isinstance(hr, (int, float)) else "")
            + (f"  evictions {w['preemptions']}"
               if isinstance(w.get("preemptions"), int)
               and w["preemptions"] else "")
            + ("  [" + ", ".join(flags) + "]" if flags else ""))
    for s in timeline["stragglers"]:
        lines.append(f"  straggler step {s.get('step')}: "
                     f"{_ms(s.get('dur_ms'), 2)} "
                     f"({s.get('ratio_to_median', '?')}x rolling median)")
    for s in timeline.get("swaps", []):
        src = s.get("swap_source")
        lines.append(f"  swap at step {s.get('step')}"
                     + (f" from {src}" if src else "")
                     + ": weights hot-swapped (contents-only; in-flight "
                       "streams kept)")
    for s in timeline.get("replans", []):
        deferred = s.get("deferred_knobs") or []
        lines.append(f"  replan at step {s.get('step')}: "
                     f"{s.get('plan_from')} -> {s.get('plan_to')} "
                     f"({s.get('replan_trigger')}; live knobs applied"
                     + (", deferred: " + ", ".join(deferred)
                        if deferred else "")
                     + ")")
    return "\n".join(lines)


def render(summary: Dict[str, Any]) -> str:
    """Human-readable step-timeline summary."""
    lines = [f"monitor report: {summary['num_records']} records, "
             f"{summary['num_steps']} steps"]
    st = summary.get("step_time_s")
    if st:
        lines.append(f"  step time   best {st['best']*1e3:.2f} ms   "
                     f"mean {st['mean']*1e3:.2f} ms   "
                     f"worst {st['worst']*1e3:.2f} ms")
    tps = summary.get("tokens_per_s")
    if tps:
        lines.append(f"  tokens/s    best {tps['best']:.1f}   "
                     f"mean {tps['mean']:.1f}")
    if "mfu" in summary:
        lines.append(f"  mfu         {summary['mfu']:.4f}  "
                     f"(model {summary['model_tflops']:.2f} TFLOP/s vs "
                     f"{summary['meta'].get('device_kind')} spec peak)")
    elif "model_tflops" in summary:
        lines.append(f"  model flops {summary['model_tflops']:.2f} TFLOP/s "
                     f"(no spec peak for this device; MFU omitted)")
    if "overflow_rate" in summary:
        lines.append(f"  overflow    {summary['overflow_rate']:.4f} "
                     f"skipped steps/step"
                     + (f", loss scale now {summary['loss_scale_last']:g}"
                        if "loss_scale_last" in summary else ""))
    pipe = summary.get("pipeline")
    if pipe and pipe.get("bubble_fraction") is not None:
        sched = pipe.get("schedule")
        step_b = pipe.get("bubble_fraction_step")
        lines.append(f"  pipeline    bubble {100*pipe['bubble_fraction']:.2f}%"
                     f"  (M={pipe.get('num_microbatches')} "
                     f"S={pipe.get('pipeline_size')} "
                     f"v={pipe.get('virtual_chunks')}"
                     + (f" sched={sched}" if sched else "")
                     + (f" step-bubble {100*step_b:.2f}%"
                        if isinstance(step_b, (int, float)) else "")
                     + ")")
        if pipe.get("per_tick_wall_s") is not None:
            lines.append(f"  pipeline    per-(microbatch,stage) tick "
                         f"{pipe['per_tick_wall_s']*1e3:.3f} ms wall")
    for name, fields in sorted(summary.get("collectives", {}).items()):
        calls = fields.get("calls", 0)
        nbytes = fields.get("bytes", 0)
        lines.append(f"  collective  {name}: {calls:g} calls"
                     + (f", {nbytes/1e6:.2f} MB" if nbytes else "")
                     + "  (per traced program)")
    dec = summary.get("decode")
    if dec:
        if dec.get("status") == "SKIP":
            lines.append(f"  decode      SKIP({dec.get('reason', '?')})")
        else:
            parts = []
            if isinstance(dec.get("tokens_per_s"), (int, float)):
                parts.append(f"{dec['tokens_per_s']:.1f} tok/s/chip")
            if isinstance(dec.get("prefill_ms"), (int, float)):
                parts.append(f"prefill {dec['prefill_ms']:.2f} ms")
            if isinstance(dec.get("vs_naive"), (int, float)):
                parts.append(f"{dec['vs_naive']:.2f}x vs naive recompute")
            if dec.get("skipped"):
                parts.append("skipped: " + ", ".join(dec["skipped"]))
            lines.append("  decode      " + "   ".join(parts))
    lsb = summary.get("longseq_bias")
    if lsb:
        if lsb.get("status") == "SKIP":
            lines.append(
                f"  longseq-bias SKIP({lsb.get('reason', '?')})")
        else:
            parts = []
            if isinstance(lsb.get("tokens_per_s"), (int, float)):
                parts.append(f"{lsb['tokens_per_s']:.1f} tok/s bucketed")
            if isinstance(lsb.get("vs_materialized"), (int, float)):
                parts.append(f"{lsb['vs_materialized']:.2f}x vs "
                             f"materialized")
            if isinstance(lsb.get("hbm_peak_mb"), (int, float)):
                parts.append(f"HBM peak {lsb['hbm_peak_mb']:.0f} MB")
            if lsb.get("skipped"):
                parts.append("skipped: " + ", ".join(lsb["skipped"]))
            lines.append("  longseq-bias " + "   ".join(parts))
    srv = summary.get("serve")
    if srv:
        if srv.get("status") == "SKIP":
            lines.append(f"  serve       SKIP({srv.get('reason', '?')})")
        else:
            parts = []
            if isinstance(srv.get("tokens_per_s"), (int, float)):
                parts.append(f"{srv['tokens_per_s']:.1f} tok/s under churn")
            if isinstance(srv.get("latency_p50_ms"), (int, float)) and \
                    isinstance(srv.get("latency_p99_ms"), (int, float)):
                parts.append(f"p50/p99 {srv['latency_p50_ms']:.2f}/"
                             f"{srv['latency_p99_ms']:.2f} ms/token")
            if isinstance(srv.get("ttft_p50_ms"), (int, float)):
                parts.append(f"ttft p50 {srv['ttft_p50_ms']:.2f} ms")
            if isinstance(srv.get("occupancy_pct"), (int, float)):
                parts.append(f"occ {srv['occupancy_pct']:.0f}%")
            if srv.get("skipped"):
                parts.append("skipped: " + ", ".join(srv["skipped"]))
            lines.append("  serve       " + "   ".join(parts))
        anom = srv.get("serve_anomaly")
        if isinstance(anom, dict):
            flags = _anomaly_flags(anom)
            lines.append("  serve       anomalies: "
                         + (", ".join(flags) if flags else "none"))
    swin = summary.get("serve_window")
    if swin:
        parts = [f"{swin['windows']} windows"]
        if isinstance(swin.get("tokens_per_s"), (int, float)):
            parts.append(f"last {swin['tokens_per_s']:.1f} tok/s")
        if isinstance(swin.get("queue_depth"), (int, float)):
            parts.append(f"queue {swin['queue_depth']:g}")
        if isinstance(swin.get("occupancy_pct"), (int, float)):
            parts.append(f"occ {swin['occupancy_pct']:.0f}%")
        anom = swin.get("serve_anomaly")
        if isinstance(anom, dict):
            flags = _anomaly_flags(anom)
            if flags:
                parts.append("anomalies: " + ", ".join(flags))
        lines.append("  serve-win   " + "   ".join(parts))
    pb = summary.get("pipeline_bench")
    if pb:
        if pb.get("status") == "SKIP":
            lines.append(f"  pipeline-bench SKIP({pb.get('reason', '?')})")
        else:
            parts = []
            if pb.get("schedule"):
                parts.append(f"{pb['schedule']}")
            if isinstance(pb.get("tokens_per_s"), (int, float)):
                parts.append(f"{pb['tokens_per_s']:.1f} tok/s")
            if isinstance(pb.get("vs_1f1b"), (int, float)):
                parts.append(f"{pb['vs_1f1b']:.2f}x vs 1f1b")
            if isinstance(pb.get("bubble_pct"), (int, float)):
                parts.append(f"bubble {pb['bubble_pct']:.1f}%")
            elif isinstance(pb.get("bubble_pct_geometry"), (int, float)):
                parts.append(
                    f"bubble {pb['bubble_pct_geometry']:.1f}% (geometry)")
            if isinstance(pb.get("p2p_bytes_per_step"), (int, float)):
                parts.append(f"p2p {pb['p2p_bytes_per_step']/1e6:.2f} MB/step")
            if pb.get("skipped"):
                parts.append("skipped: " + ", ".join(pb["skipped"]))
            lines.append("  pipeline-bench " + "   ".join(parts))
    tpo = summary.get("tp_overlap")
    if tpo:
        if tpo.get("status") == "SKIP":
            lines.append(f"  tp-overlap  SKIP({tpo.get('reason', '?')})")
        else:
            parts = []
            if isinstance(tpo.get("tokens_per_s"), (int, float)):
                parts.append(f"{tpo['tokens_per_s']:.1f} tok/s overlapped")
            if isinstance(tpo.get("vs_blocking"), (int, float)):
                parts.append(f"{tpo['vs_blocking']:.2f}x vs blocking")
            if isinstance(tpo.get("tp"), (int, float)):
                parts.append(f"tp={tpo['tp']:g}")
            if tpo.get("skipped"):
                parts.append("skipped: " + ", ".join(tpo["skipped"]))
            lines.append("  tp-overlap  " + "   ".join(parts))
    spc = summary.get("spec")
    if spc:
        if spc.get("status") == "SKIP":
            lines.append(f"  spec        SKIP({spc.get('reason', '?')})")
        else:
            parts = []
            if isinstance(spc.get("tokens_per_s_request"), (int, float)):
                parts.append(
                    f"{spc['tokens_per_s_request']:.1f} tok/s/request")
            if isinstance(spc.get("speedup"), (int, float)):
                parts.append(f"{spc['speedup']:.2f}x vs non-spec")
            if isinstance(spc.get("acceptance_rate"), (int, float)):
                parts.append(
                    f"accept {100 * spc['acceptance_rate']:.0f}%"
                    + (f" (k={spc['draft_k']:g})"
                       if isinstance(spc.get("draft_k"), (int, float))
                       else ""))
            if spc.get("drafter"):
                parts.append(f"drafter {spc['drafter']}")
            if isinstance(spc.get("kv_quant_logit_err"), (int, float)):
                parts.append(
                    f"int8-KV |Δlogit| {spc['kv_quant_logit_err']:.3g}")
            if spc.get("skipped"):
                parts.append("skipped: " + ", ".join(spc["skipped"]))
            lines.append("  spec        " + "   ".join(parts))
    tps = summary.get("tp_serve")
    if tps:
        if tps.get("status") == "SKIP":
            lines.append(f"  tp-serve    SKIP({tps.get('reason', '?')})")
        else:
            parts = []
            if isinstance(tps.get("tokens_per_s"), (int, float)):
                parts.append(f"{tps['tokens_per_s']:.1f} tok/s")
            if isinstance(tps.get("tp"), (int, float)):
                parts.append(f"tp={tps['tp']:g}")
            if isinstance(tps.get("pool_mb_per_shard"), (int, float)):
                parts.append(
                    f"pool {tps['pool_mb_per_shard']:.1f} MB/shard")
            if isinstance(tps.get("collective_bytes_per_step"),
                          (int, float)):
                parts.append(
                    f"{tps['collective_bytes_per_step'] / 1024:.1f} "
                    f"KiB coll/step")
            if isinstance(tps.get("handoff_transfer_bytes"),
                          (int, float)):
                hand = (f"handoff {tps['handoff_transfer_bytes']} B"
                        + (f"/{tps['handoff_blocks']:g} blk"
                           if isinstance(tps.get("handoff_blocks"),
                                         (int, float)) else ""))
                if isinstance(tps.get("handoff_transfer_ms"),
                              (int, float)):
                    hand += f" in {tps['handoff_transfer_ms']:.1f}ms"
                parts.append(hand)
            for flag in ("greedy_parity", "handoff_parity"):
                if tps.get(flag) is False:
                    parts.append(f"{flag.replace('_', ' ')} BROKEN")
            if tps.get("skipped"):
                parts.append("skipped: " + ", ".join(tps["skipped"]))
            lines.append("  tp-serve    " + "   ".join(parts))
    pl = summary.get("plan")
    if pl:
        parts = []
        if pl.get("chosen_describe"):
            parts.append(f"chose {pl['chosen_describe']}")
        if isinstance(pl.get("predicted_step_ms"), (int, float)):
            parts.append(f"pred {pl['predicted_step_ms']:.3f} ms")
        if isinstance(pl.get("measured_step_ms"), (int, float)):
            parts.append(f"meas {pl['measured_step_ms']:.3f} ms")
        if isinstance(pl.get("predicted_vs_measured_err_pct"),
                      (int, float)):
            parts.append(f"err {pl['predicted_vs_measured_err_pct']:.1f}%")
        if isinstance(pl.get("feasible"), int):
            parts.append(f"{pl['feasible']}/{pl.get('searched', '?')} "
                         f"feasible")
        if pl.get("confidence"):
            parts.append(pl["confidence"])
        if pl.get("uncalibrated"):
            parts.append("uncalibrated: " + ", ".join(pl["uncalibrated"]))
        if pl.get("status") == "SKIP":
            parts.append(f"SKIP({pl.get('reason', '?')})")
        lines.append("  plan        " + "   ".join(parts))
    ck = summary.get("ckpt")
    if ck:
        if ck.get("status") == "SKIP":
            lines.append(f"  ckpt        SKIP({ck.get('reason', '?')})")
        else:
            parts = []
            if isinstance(ck.get("save_overhead_pct"), (int, float)):
                parts.append(
                    f"save overhead {ck['save_overhead_pct']:.2f}%/step")
            if isinstance(ck.get("snapshot_ms"), (int, float)):
                parts.append(f"snapshot {ck['snapshot_ms']:.2f} ms")
            if isinstance(ck.get("write_ms"), (int, float)):
                parts.append(f"write {ck['write_ms']:.2f} ms (async)")
            if isinstance(ck.get("bytes_written"), (int, float)):
                parts.append(f"{ck['bytes_written']/1e6:.2f} MB")
            if ck.get("bitwise_resume_ok") is True:
                parts.append("bitwise-resume ok")
            if ck.get("elastic_resume_ok") is True:
                parts.append("elastic ok")
            if ck.get("skipped"):
                parts.append("skipped: " + ", ".join(ck["skipped"]))
            lines.append("  ckpt        " + "   ".join(parts))
    for gate in summary.get("gates", []):
        skipped = (", skipped: " + ", ".join(gate["skipped"])
                   if gate["skipped"] else "")
        lines.append(f"  gate        {gate['name']}: "
                     f"{'OK' if gate['ok'] else 'FAILED'}{skipped}")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m apex_tpu.monitor",
        description="apex_tpu telemetry tools")
    sub = parser.add_subparsers(dest="command", required=True)
    rep = sub.add_parser("report", help="summarize a monitor JSONL stream")
    rep.add_argument("path", help="events.jsonl produced with monitoring on")
    rep.add_argument("--json", action="store_true",
                     help="print the summary as one JSON object")
    rep.add_argument("--anatomy", action="store_true",
                     help="per-step anatomy (% compute / collective-exposed"
                          " / bubble / host gap per device) from the span "
                          "stream joined with a jax.profiler trace")
    rep.add_argument("--trace", metavar="LOGDIR",
                     help="profiler log dir to join spans against "
                          "(required with --anatomy)")
    rep.add_argument("--serve-timeline", action="store_true",
                     help="per-request serving lifecycle (serve_event "
                          "records) + the serve_window SLO trail")
    rep.add_argument("--attribution", action="store_true",
                     help="per-request e2e latency decomposition (queue/"
                          "prefill/decode/spec/preempt/swap components "
                          "from the serve_event trail) as a validated "
                          "serve_attribution record")
    trc = sub.add_parser(
        "trace", help="export the stream as Chrome trace-event JSON "
                      "(chrome://tracing / Perfetto): one track per "
                      "rank, one per request")
    trc.add_argument("path", help="events.jsonl produced with monitoring "
                                  "on")
    trc.add_argument("--out", default=None,
                     help="output path (default: <path>.trace.json; a "
                          ".gz suffix gzips — both viewers load it)")
    trc.add_argument("--device-trace", metavar="LOGDIR", default=None,
                     help="jax.profiler log dir whose device events ride "
                          "along on offset process ids (the span "
                          "scope-prefix join)")
    args = parser.parse_args(argv)

    with open(args.path) as fh:
        records = read_records(fh)
    if args.command == "trace":
        return _trace_export_main(args, records)
    summary = aggregate(records)

    timeline = None
    if args.serve_timeline:
        timeline = serve_timeline(records)
        if not (timeline["requests"] or timeline["windows"]):
            print("error: stream carries no serve_event/serve_window "
                  "records (serve with a ServeTelemetry attached and "
                  "the monitor enabled)", file=sys.stderr)
            return 2
        summary["serve_timeline"] = timeline

    attribution = None
    attribution_skip = None
    if args.attribution:
        attribution = serve_attribution_record(records)
        if attribution is None:
            # the requested-section-absent contract: an explicit
            # SKIP(reason) line / stanza, never a silent empty section
            attribution_skip = _ATTRIBUTION_SKIP_REASON
            summary["serve_attribution"] = {
                "status": "SKIP", "reason": attribution_skip}
        else:
            summary["serve_attribution"] = attribution

    anatomy_rows = None
    if args.anatomy:
        if not args.trace:
            parser.error("--anatomy needs --trace LOGDIR (the directory "
                         "passed to jax.profiler.start_trace)")
        # the join lives in prof (it reads chrome traces); imported lazily
        # so the plain report never pays for it
        from apex_tpu.prof import trace_reader

        spans = [r for r in records if r.get("kind") == "span"]
        try:
            events = trace_reader.read_trace(args.trace)
        except FileNotFoundError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        anatomy_rows = trace_reader.step_anatomy(spans, events)
        summary["anatomy"] = anatomy_rows

    if args.json:
        print(json.dumps(summary))
    else:
        print(render(summary))
        if timeline is not None:
            print(format_serve_timeline(timeline))
        if attribution is not None:
            print(format_attribution(attribution))
        elif attribution_skip is not None:
            print(f"serve attribution: SKIP({attribution_skip})")
        if anatomy_rows is not None:
            from apex_tpu.prof.trace_reader import format_anatomy

            print("step anatomy (% of step wall):")
            print(format_anatomy(anatomy_rows))
    return 0


def _trace_export_main(args, records: List[Dict[str, Any]]) -> int:
    """``python -m apex_tpu.monitor trace events.jsonl [--out ...]`` —
    merge the stream (plus an optional profiler device trace) into one
    Chrome trace-event JSON file."""
    from apex_tpu.monitor import trace as trace_lib

    device_events = None
    if args.device_trace:
        from apex_tpu.prof import trace_reader
        try:
            device_events = trace_reader.read_trace(args.device_trace)
        except FileNotFoundError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    doc = trace_lib.chrome_trace(records, device_events=device_events)
    slices = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    if not slices:
        # nothing written: an empty export silently "succeeding" would
        # hide that the run never emitted span/serve_event records
        print("trace export: SKIP(stream carries no span/serve_event "
              "records to export — run with the monitor enabled, e.g. "
              "a serve with ServeTelemetry attached)")
        return 2
    out = args.out or (args.path + ".trace.json")
    trace_lib.write_chrome_trace(out, records, doc=doc)

    def _tracks(prefix: str) -> int:
        return sum(
            1 for e in doc["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "process_name"
            and str(e.get("args", {}).get("name", "")).startswith(prefix))

    print(f"wrote {len(slices)} slices ({_tracks('req ')} request "
          f"tracks, {_tracks('rank ')} rank tracks) to {out}")
    return 0


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
