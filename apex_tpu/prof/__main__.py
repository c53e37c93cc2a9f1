"""CLI: ``python -m apex_tpu.prof <logdir or .xplane.pb> [--steps N] [--top N]
[--spans events.jsonl [--anatomy] [--merged out.json]]``.

From the ``.xplane.pb`` of a ``jax.profiler`` run it prints the device time by
the program's own spans (:mod:`apex_tpu.prof.scopes`): a row a span path, ms
a step (``--steps``: the steps the trace holds) in all, in the span's own
operations, and forward, recomputed, backward and update apart. Where the
run also holds a chrome trace (``*.trace.json.gz``: the profiler writes one
where its converter is installed) it prints, after that, the top device time
sinks and the per-family roofline table — the TPU analog of
``python -m apex.pyprof.prof`` (``apex/pyprof/prof/__main__.py``) — and with
``--spans`` (a monitor JSONL stream carrying span records), ``--anatomy``
additionally prints the per-step anatomy table and ``--merged`` writes the
fused host+device chrome-trace timeline.

Exit status: 0 on success; 2 when the logdir holds no trace run (one
line on stderr naming the searched glob — a missing capture must not
read as a crash).
"""

import argparse
import sys

from apex_tpu.prof import scopes
from apex_tpu.prof.trace_reader import (
    format_anatomy,
    format_report,
    has_chrome_trace,
    read_span_stream,
    read_trace,
    step_anatomy,
    write_merged_timeline,
)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m apex_tpu.prof",
        description="Analyze a jax.profiler trace directory")
    p.add_argument("logdir",
                   help="directory passed to jax.profiler.start_trace, or an .xplane.pb")
    p.add_argument("--top", type=int, default=5,
                   help="time sinks to show (under each span, of its own operations)")
    p.add_argument("--steps", type=int, default=1,
                   help="steps the trace holds: the span report is ms a step")
    p.add_argument("--spans", metavar="EVENTS_JSONL",
                   help="monitor JSONL stream with span records to join "
                        "against the trace")
    p.add_argument("--anatomy", action="store_true",
                   help="print the per-step anatomy table (needs --spans)")
    p.add_argument("--merged", metavar="OUT_JSON",
                   help="write the merged host+device chrome trace "
                        "(needs --spans; .gz suffix gzips)")
    args = p.parse_args(argv)
    if (args.anatomy or args.merged) and not args.spans:
        p.error("--anatomy/--merged need --spans EVENTS_JSONL")

    chrome = has_chrome_trace(args.logdir)
    if args.spans and not chrome:
        p.error("--spans joins against a chrome trace; this run holds none")
    try:
        # the raw .xplane.pb, by the program's spans: what this profiler writes
        print(scopes.format_xplane_report(args.logdir, args.steps, args.top))
    except FileNotFoundError as e:
        if not chrome:
            print(f"error: {e}", file=sys.stderr)
            return 2
    if chrome:
        print(format_report(args.logdir, args.top))
        if args.spans:
            events = read_trace(args.logdir)
            spans = read_span_stream(args.spans)
            if args.anatomy:
                print()
                print("step anatomy (% of step wall):")
                print(format_anatomy(step_anatomy(spans, events)))
            if args.merged:
                write_merged_timeline(args.merged, spans, events)
                print(f"merged timeline written to {args.merged}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
