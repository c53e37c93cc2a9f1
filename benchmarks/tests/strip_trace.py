"""Cut a profiler trace down to a test fixture: one step of chip 0 and the
host events beside it, every stat removed and every name cut to its first
``NAME`` characters (an operation's name, result shape and opcode; names,
starts and durations are all ``trace_reduce`` reads). The device's ``Async
XLA Ops`` line is left out: a one-chip step has no collective in flight.
Run by hand, off the chip, where TensorFlow's copy of the ``.xplane.pb``
schema can be imported:

    python benchmarks/tests/strip_trace.py <in.xplane.pb> <out.xplane.pb> [step]

``step`` counts the events of the device's ``Steps`` line from 0 (default 1:
the second traced step, which has a step before it and one behind).
"""
import sys

DEVICE, HOST = "/device:TPU:0", "/host:CPU"
LINES = ("Steps", "XLA Modules", "XLA Ops")
NAME = 120


def window_ps(plane, step):
    """The step's [start, end) in picoseconds since the epoch."""
    for line in plane.lines:
        if line.name == "Steps":
            e = line.events[step]
            start = line.timestamp_ns * 1000 + e.offset_ps
            return start, start + e.duration_ps
    raise SystemExit(f"{plane.name} has no Steps line")


def strip(plane, lo, hi, lines=None):
    """Keep the events of ``lines`` that lie inside [lo, hi) — on the host
    (``lines`` None: every line) those that overlap it — and only their
    names, starts and durations."""
    del plane.stats[:]
    plane.stat_metadata.clear()
    kept = [l for l in plane.lines if lines is None or l.name in lines]
    used = set()
    for line in kept:
        base = line.timestamp_ns * 1000
        ends = [(base + e.offset_ps, base + e.offset_ps + e.duration_ps, e)
                for e in line.events]
        inside = [e for a, b, e in ends
                  if (a < hi and b > lo if lines is None else a >= lo and b <= hi)]
        for e in inside:
            del e.stats[:]
            used.add(e.metadata_id)
        del line.events[:]
        line.events.extend(inside)
    kept = [l for l in kept if l.events]
    del plane.lines[:]
    plane.lines.extend(kept)
    for key in [k for k in plane.event_metadata if k not in used]:
        del plane.event_metadata[key]
    for key, meta in plane.event_metadata.items():
        name = meta.name[:NAME]
        meta.Clear()
        meta.id, meta.name = key, name


def main(src, dst, step=1):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    planes = {p.name: p for p in space.planes}
    lo, hi = window_ps(planes[DEVICE], int(step))
    strip(planes[DEVICE], lo, hi, LINES)
    strip(planes[HOST], lo, hi)
    out = xplane_pb2.XSpace()
    out.planes.extend([planes[DEVICE], planes[HOST]])
    blob = out.SerializeToString()
    with open(dst, "wb") as f:
        f.write(blob)
    print(f"{dst}: {len(blob)} bytes, step {step} = "
          f"{(hi - lo) / 1e9:.3f} ms")


if __name__ == "__main__":
    main(*sys.argv[1:])
