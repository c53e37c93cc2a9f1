"""Real-trace post-processor: ingest a ``jax.profiler`` run and emit
per-op / per-family time+cost tables.

The pyprof pipeline analog (``apex/pyprof/parse/{db,nvvp,kernel}.py`` reads
the nvprof SQLite DB and correlates kernels with NVTX ranges;
``apex/pyprof/prof/__main__.py`` then prints per-kernel FLOPs/bytes). Here
the source of truth is the ``trace.json.gz`` chrome trace that
``jax.profiler.stop_trace`` writes under ``<logdir>/plugins/profile/<run>/``:

* device rows (process ``/device:TPU:N``, thread ``XLA Ops``) carry one
  complete-event per executed HLO, named with the full ``named_scope`` path
  — the correlation step the reference needs a database join for comes free;
* :func:`op_records` turns them into compact records, folding multiple
  executions of the same op;
* :func:`summarize` ranks time sinks and aggregates op families via
  :func:`apex_tpu.prof.analyzer.analyze_ops` (whose hot path is the native
  C++ aggregator ``csrc/trace_analyzer.cpp`` for large traces).

CLI: ``python -m apex_tpu.prof <logdir> [--top N]``.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple


# the arg keys the analysis pipeline consumes; both trace parsers (the
# native csrc/trace_parser.cpp and the Python fallback) restrict
# TraceEvent.args to these so behavior doesn't depend on which is built
WANTED_ARGS = frozenset((
    "model_flops", "bytes_accessed", "raw_bytes_accessed", "hlo_category",
    "source", "flops", "bytes", "bytes accessed",
))


@dataclasses.dataclass
class TraceEvent:
    name: str
    start_us: float
    dur_us: float
    device: str       # e.g. "/device:TPU:0"
    track: str        # e.g. "XLA Ops"
    args: dict        # WANTED_ARGS subset of the raw event args


def _latest_run_dir(log_dir: str) -> str:
    pattern = os.path.join(log_dir, "plugins", "profile", "*")
    runs = sorted(glob.glob(pattern))
    if not runs:
        raise FileNotFoundError(
            f"no profiler runs under {log_dir!r} (searched {pattern!r}; "
            "pass the directory given to jax.profiler.start_trace)")
    return runs[-1]


def _trace_file(run_dir: str) -> str:
    pattern = os.path.join(run_dir, "*.trace.json.gz")
    files = glob.glob(pattern)
    if not files:
        raise FileNotFoundError(f"no chrome trace (searched {pattern!r})")
    return files[0]


def has_chrome_trace(log_dir: str) -> bool:
    """Whether the newest run under ``log_dir`` holds a ``*.trace.json.gz``
    (jax 0.9 writes the ``.xplane.pb`` alone; ``apex_tpu.prof.scopes`` reads
    that)."""
    try:
        _trace_file(_latest_run_dir(log_dir))
    except FileNotFoundError:
        return False
    return True


def read_trace(log_dir: str) -> List[TraceEvent]:
    """Parse the newest run's chrome trace into device events.

    IO goes through the native parser (``csrc/trace_parser.cpp``) when
    built — one C pass replaces gzip+json.load, the dominant cost on real
    multi-MB traces; the pure-Python path is the fallback."""
    path = _trace_file(_latest_run_dir(log_dir))

    from apex_tpu import native as _native
    if _native.available():
        try:
            return [
                TraceEvent(
                    name=e["name"], start_us=e["ts"], dur_us=e["dur"],
                    device=e["device"], track=e["track"],
                    args=e.get("args") or {},
                )
                for e in _native.parse_trace(path)
            ]
        except (ValueError, KeyError):
            pass  # malformed for the fast path; fall through to Python

    with gzip.open(path, "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])

    # metadata pass: pid -> process name, (pid, tid) -> thread name
    procs = {}
    threads = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e.get("pid")] = e.get("args", {}).get("name", "")
        elif e.get("name") == "thread_name":
            threads[(e.get("pid"), e.get("tid"))] = e.get("args", {}).get("name", "")

    out: List[TraceEvent] = []
    for e in events:
        if e.get("ph") != "X":
            continue
        pid = e.get("pid")
        dev = procs.get(pid, "")
        args = e.get("args") or {}
        out.append(TraceEvent(
            name=e.get("name", ""),
            start_us=float(e.get("ts", 0.0)),
            dur_us=float(e.get("dur", 0.0)),
            device=dev,
            track=threads.get((pid, e.get("tid")), ""),
            args={k: v for k, v in args.items() if k in WANTED_ARGS},
        ))
    return out


def device_op_events(events: Sequence[TraceEvent]) -> List[TraceEvent]:
    """The per-HLO device rows — the analog of the kernels table pyprof
    correlates against (``parse/db.py``)."""
    return [
        e for e in events
        if "/device:" in e.device and e.track in ("XLA Ops", "Async XLA Ops")
    ]


def _scope_of(name: str) -> str:
    """'encoder/block/attention/dot.7' -> 'encoder/block/attention'."""
    return name.rsplit("/", 1)[0] if "/" in name else ""


def _f(args: dict, *keys) -> float:
    for k in keys:
        v = args.get(k)
        if v not in (None, ""):
            try:
                return float(v)
            except (TypeError, ValueError):
                pass
    return 0.0


def op_records(events: Sequence[TraceEvent]) -> List[dict]:
    """Fold executions into per-op records consumable by ``analyze_ops``.

    XProf device events carry XLA's own per-op cost model in args —
    ``model_flops``, ``bytes_accessed``, ``hlo_category``, and the Python
    ``source`` line the HLO was traced from (the correlation pyprof does
    with a database join, ``apex/pyprof/parse/db.py``). Plain traces
    without those keys still aggregate by name/time.
    """
    acc: Dict[str, List] = defaultdict(lambda: [0.0, 0.0, 0.0, 0.0, "", ""])
    for e in device_op_events(events):
        a = acc[e.name]
        a[0] += 1
        a[1] += e.dur_us / 1e6
        a[2] += _f(e.args, "model_flops", "flops")
        a[3] += _f(e.args, "bytes_accessed", "raw_bytes_accessed",
                   "bytes accessed", "bytes")
        a[4] = a[4] or str(e.args.get("hlo_category", "") or "")
        a[5] = a[5] or str(e.args.get("source", "") or "")
    return [
        {"name": name, "count": int(c), "time_s": t, "flops": f, "bytes": b,
         "scope": _scope_of(name), "category": cat, "source": src}
        for name, (c, t, f, b, cat, src) in acc.items()
    ]


def by_source(recs: Sequence[dict]) -> List[dict]:
    """Roll device time up to the Python source line that emitted the HLO —
    model-code attribution (the reference gets this from NVTX call-site
    JSON, ``apex/pyprof/nvtx/nvmarker.py``). Records without a source
    (renamed/fused away) aggregate under ``""`` and are dropped. Container
    rows (while/conditional bodies, async wrappers) span their children and
    are excluded — they would otherwise double-count the whole loop body
    onto the ``lax.scan`` call site."""
    from apex_tpu.prof.analyzer import CONTAINER_FAMILIES, _family_of

    acc: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0.0, 0.0, 0.0])
    for r in recs:
        src = r.get("source", "")
        if not src:
            continue
        if _family_of(r["name"], r.get("category", "")) in CONTAINER_FAMILIES:
            continue
        a = acc[src]
        a[0] += r["count"]
        a[1] += r["time_s"]
        a[2] += r.get("flops", 0.0)
        a[3] += r.get("bytes", 0.0)
    out = [
        {"source": s, "count": int(c), "time_s": t, "flops": f, "bytes": b}
        for s, (c, t, f, b) in acc.items()
    ]
    out.sort(key=lambda r: -r["time_s"])
    return out


def _analyze_run(log_dir: str):
    """(all records by time desc, non-container sinks, per-family stats)
    — the shared core of summarize/format_report. Container rows
    (while/conditional bodies, which span their children on the same
    track) are excluded from the sink ranking to avoid double counting."""
    from apex_tpu.prof.analyzer import (CONTAINER_FAMILIES, _family_of,
                                        analyze_ops)

    recs = op_records(read_trace(log_dir))
    recs.sort(key=lambda r: -r["time_s"])
    fams = analyze_ops(recs)
    sinks = [r for r in recs
             if _family_of(r["name"], r.get("category", ""))
             not in CONTAINER_FAMILIES]
    return recs, sinks, fams


def summarize(log_dir: str, top: int = 5) -> Tuple[List[dict], Dict[str, "OpStats"]]:
    """(top-K time sinks, per-family stats) for the newest run."""
    _, sinks, fams = _analyze_run(log_dir)
    return sinks[:top], fams


def format_report(log_dir: str, top: int = 5) -> str:
    """pyprof.prof-style text report: top time sinks (with the Python
    source line each HLO traces to), top source-line rollup, and the
    per-family roofline table."""
    from apex_tpu.prof.analyzer import CONTAINER_FAMILIES, report

    recs, sinks, fams = _analyze_run(log_dir)
    if not recs:
        return ("no per-HLO device events in trace — the CPU backend "
                "exports host events only; capture on TPU/GPU for op-level "
                "analysis")
    sinks = sinks[:top]
    lines = [f"top {len(sinks)} device time sinks:"]
    total = sum(s.time_s for f, s in fams.items()
                if f not in CONTAINER_FAMILIES) or 1.0
    for r in sinks:
        src = r.get("source", "")
        src = f"  [{_short_source(src)}]" if src else ""
        lines.append(
            f"  {r['time_s']*1e3:9.3f} ms  {100*r['time_s']/total:5.1f}%  "
            f"x{r['count']:<5d} {r['name'][:70]}{src}"
        )
    srcs = [r for r in by_source(recs) if r["source"]][:top]
    if srcs:
        lines.append("")
        lines.append(f"top {len(srcs)} source lines by device time:")
        for r in srcs:
            lines.append(
                f"  {r['time_s']*1e3:9.3f} ms  {100*r['time_s']/total:5.1f}%  "
                f"{_short_source(r['source'])}"
            )
    lines.append("")
    lines.append(report(fams))
    return "\n".join(lines)


def _short_source(src: str) -> str:
    """/abs/path/pkg/mod.py:12 -> pkg/mod.py:12 (last two path segments)."""
    head, _, line = src.rpartition(":")
    parts = (head or src).split(os.sep)
    short = os.sep.join(parts[-2:])
    return f"{short}:{line}" if head else short


# --- host↔device correlation (step anatomy) -----------------------------------
#
# The monitor's span stream (monitor.spans) records host enter/exit
# windows whose names are named-scope paths — the same paths device-trace
# op names carry as prefixes. That prefix IS the join: no database
# correlation pass (the reference needs apex/pyprof/parse/db.py), just a
# string match. The functions below fuse the two halves into per-step
# anatomy rows (% compute / collective-exposed / bubble / host gap, per
# device) and one merged chrome-trace timeline.


def read_span_stream(source) -> List[dict]:
    """The ``span`` records of a monitor JSONL stream (a path or an
    iterable of lines), in emission order."""
    if isinstance(source, str):
        with open(source) as fh:
            lines = fh.read().splitlines()
    else:
        lines = list(source)
    spans = []
    for line in lines:
        line = line.strip()
        if not line:
            continue
        rec = json.loads(line)
        if rec.get("kind") == "span":
            spans.append(rec)
    return spans


def host_step_spans(spans: Sequence[dict]) -> List[dict]:
    """The host-phase step windows: spans whose final path segment is
    ``step`` and that were NOT recorded under a trace (traced spans'
    host durations measure tracing, not execution), by start time."""
    return sorted(
        (s for s in spans
         if s.get("name", "").rsplit("/", 1)[-1] == "step"
         and not s.get("traced")),
        key=lambda s: s.get("t0_ns", 0))


def correlate(spans: Sequence[dict],
              events: Sequence[TraceEvent]) -> Dict[str, dict]:
    """Join device op events onto span scope paths.

    A device event belongs to span path ``p`` when its name is ``p`` or
    starts with ``p + "/"`` (named-scope nesting). Returns
    ``{span_path: {"span": record, "count", "time_s", "flops", "bytes",
    "events": [...]}}`` — one entry per distinct span path (a traced span
    re-emitted per retrace still yields one entry)."""
    out: Dict[str, dict] = {}
    dev = device_op_events(events)
    for s in spans:
        path = s.get("name", "")
        if not path or path in out:
            continue
        matched = [e for e in dev
                   if e.name == path or e.name.startswith(path + "/")]
        out[path] = {
            "span": s,
            "count": len(matched),
            "time_s": sum(e.dur_us for e in matched) / 1e6,
            "flops": sum(_f(e.args, "model_flops", "flops")
                         for e in matched),
            "bytes": sum(_f(e.args, "bytes_accessed", "raw_bytes_accessed",
                            "bytes accessed", "bytes") for e in matched),
            "events": matched,
        }
    return out


def split_steps(events: Sequence[TraceEvent],
                n: int) -> List[List[TraceEvent]]:
    """Partition one device's op events into ``n`` execution windows by
    cutting at the ``n−1`` largest idle gaps. One jitted step is one
    dense burst of device work; the gaps between bursts are host time —
    the same boundary the host step spans measure — so cutting at the
    widest gaps recovers the per-step windows without any clock
    alignment between host and device."""
    evs = sorted(events, key=lambda e: e.start_us)
    if n <= 1 or len(evs) <= 1:
        return [evs] if evs else []
    gaps = []  # (idle gap before event i, i)
    frontier = evs[0].start_us + evs[0].dur_us
    for i in range(1, len(evs)):
        gaps.append((evs[i].start_us - frontier, i))
        frontier = max(frontier, evs[i].start_us + evs[i].dur_us)
    cuts = sorted(i for _, i in sorted(gaps, reverse=True)[:n - 1])
    windows = []
    prev = 0
    for c in cuts:
        windows.append(evs[prev:c])
        prev = c
    windows.append(evs[prev:])
    return windows


def _merge_intervals(intervals):
    """Sorted merge of (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _total(merged) -> float:
    return sum(e - s for s, e in merged)


def _intersect_total(a, b) -> float:
    """Total overlap length of two MERGED interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def step_anatomy(spans: Sequence[dict],
                 events: Sequence[TraceEvent]) -> List[dict]:
    """Fuse host step spans with device op events into per-(step, device)
    anatomy rows.

    Per device, the op events split into as many execution windows as
    there are host step spans (:func:`split_steps`); window *i* pairs
    with step span *i* (both are in time order — no host↔device clock
    alignment needed). Within a window, with ``K`` = the union of
    compute-op intervals (every non-container, non-collective family)
    and ``C`` = the union of collective intervals:

    * ``compute_s``             = \\|K\\|
    * ``collective_exposed_s``  = \\|C\\| − \\|C ∩ K\\| (collective time no
      compute hides — overlapped collectives cost nothing here)
    * ``bubble_s``              = window extent − \\|K ∪ C\\| (device idle
      inside the step)
    * ``host_gap_s``            = host wall − extent (step time the
      device never saw: dispatch, host work between launches)

    and the four percentages are of the host wall, so they sum to 100
    (when the host wall is shorter than the device extent — mismatched
    streams — the extent is the denominator and ``host_gap`` is 0).
    """
    steps = host_step_spans(spans)
    if not steps:
        return []
    from apex_tpu.prof.analyzer import CONTAINER_FAMILIES, _family_of

    by_device: Dict[str, List[TraceEvent]] = defaultdict(list)
    for e in device_op_events(events):
        by_device[e.device].append(e)

    rows = []
    for device in sorted(by_device):
        windows = split_steps(by_device[device], len(steps))
        for i, (span, win) in enumerate(zip(steps, windows)):
            comp, coll = [], []
            for e in win:
                fam = _family_of(e.name, e.args.get("hlo_category", ""))
                if fam in CONTAINER_FAMILIES:
                    continue
                iv = (e.start_us / 1e6, (e.start_us + e.dur_us) / 1e6)
                (coll if fam == "collective" else comp).append(iv)
            K = _merge_intervals(comp)
            C = _merge_intervals(coll)
            busy = _merge_intervals(comp + coll)
            compute_s = _total(K)
            exposed_s = _total(C) - _intersect_total(C, K)
            extent = ((max(e.start_us + e.dur_us for e in win)
                       - min(e.start_us for e in win)) / 1e6 if win else 0.0)
            bubble_s = extent - _total(busy)
            wall_s = span.get("dur_ns", 0) / 1e9
            denom = max(wall_s, extent)
            host_gap_s = max(0.0, wall_s - extent)
            pct = (lambda x: 100.0 * x / denom) if denom else (lambda x: 0.0)
            rows.append({
                "step": span.get("step", i),
                "device": device,
                "wall_s": wall_s,
                "compute_s": compute_s,
                "collective_exposed_s": exposed_s,
                "bubble_s": bubble_s,
                "host_gap_s": host_gap_s,
                "compute_pct": pct(compute_s),
                "collective_exposed_pct": pct(exposed_s),
                "bubble_pct": pct(bubble_s),
                "host_gap_pct": pct(host_gap_s),
            })
    return rows


def format_anatomy(rows: Sequence[dict]) -> str:
    """Text table of :func:`step_anatomy` rows — what ``python -m
    apex_tpu.monitor report --anatomy`` prints."""
    if not rows:
        return ("no anatomy rows: need host step spans in the stream AND "
                "per-HLO device events in the trace (CPU traces are "
                "host-only; capture on TPU/GPU)")
    lines = [f"{'step':>5} {'device':<18}{'wall ms':>9}{'compute%':>10}"
             f"{'coll-exp%':>11}{'bubble%':>9}{'host-gap%':>11}"]
    for r in rows:
        lines.append(
            f"{r['step']:>5} {r['device']:<18}{r['wall_s']*1e3:>9.3f}"
            f"{r['compute_pct']:>10.2f}{r['collective_exposed_pct']:>11.2f}"
            f"{r['bubble_pct']:>9.2f}{r['host_gap_pct']:>11.2f}")
    return "\n".join(lines)


def merged_timeline(spans: Sequence[dict],
                    events: Sequence[TraceEvent]) -> dict:
    """One chrome-trace/Perfetto JSON object holding BOTH halves: the
    monitor's host spans (one track per process, trace-time spans on
    their own track) and the device op events. Host timestamps are
    monotonic-ns and device timestamps profiler-epoch µs, so the host
    track is shifted to align the first host step span with the start of
    the first device window — alignment is presentational; the anatomy
    numbers come from :func:`step_anatomy`, which never mixes the
    clocks."""
    trace_events = []
    pids: Dict[str, int] = {}

    def pid_of(name):
        if name not in pids:
            pids[name] = len(pids) + 1
            trace_events.append({"ph": "M", "pid": pids[name],
                                 "name": "process_name",
                                 "args": {"name": name}})
        return pids[name]

    dev = device_op_events(events)
    steps = host_step_spans(spans)
    offset_us = 0.0
    if spans:
        t0_host = min(s.get("t0_ns", 0) for s in spans) / 1e3
        if steps and dev:
            t0_host = steps[0]["t0_ns"] / 1e3
            offset_us = min(e.start_us for e in dev) - t0_host
        elif dev:
            offset_us = min(e.start_us for e in dev) - t0_host

    threads_named = set()

    def name_thread(pid, tid, label):
        if (pid, tid) not in threads_named:
            threads_named.add((pid, tid))
            trace_events.append({"ph": "M", "pid": pid, "tid": tid,
                                 "name": "thread_name",
                                 "args": {"name": label}})

    for s in spans:
        pid = pid_of(f"host:spans (process {s.get('process', 0)})")
        tid = 2 if s.get("traced") else 1
        name_thread(pid, tid, "spans (trace-time)" if tid == 2 else "spans")
        args = {k: v for k, v in s.items()
                if k not in ("schema", "kind", "t_s", "name", "t0_ns",
                             "dur_ns")}
        trace_events.append({
            "ph": "X", "pid": pid, "tid": tid, "name": s["name"],
            "ts": s["t0_ns"] / 1e3 + offset_us,
            "dur": s.get("dur_ns", 0) / 1e3, "args": args})

    for e in dev:
        pid = pid_of(e.device)
        name_thread(pid, 1, e.track or "XLA Ops")
        trace_events.append({
            "ph": "X", "pid": pid, "tid": 1, "name": e.name,
            "ts": e.start_us, "dur": e.dur_us, "args": dict(e.args)})
    return {"traceEvents": trace_events}


def write_merged_timeline(path: str, spans: Sequence[dict],
                          events: Sequence[TraceEvent]) -> str:
    """Write :func:`merged_timeline` as JSON (gzipped when ``path`` ends
    in ``.gz``); returns ``path``. Load it in Perfetto / chrome://tracing
    to see host spans and device kernels on one timeline."""
    data = merged_timeline(spans, events)
    if path.endswith(".gz"):
        with gzip.open(path, "wt") as fh:
            json.dump(data, fh)
    else:
        with open(path, "w") as fh:
            json.dump(data, fh)
    return path
