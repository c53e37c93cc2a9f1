"""From a profiler trace (``.xplane.pb``) to seconds: device busy time, the
operations that took most of it, the longest idle gaps with what the host was
doing meanwhile, and collective time not hidden behind compute.

Read with ``jax.profiler.ProfileData`` alone. A TPU's plane is named
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed
operation (the other lines — modules, steps, trace-me — cover the same
time again and are not summed); ``Async XLA Ops`` holds what is in flight
beside them, from each ``-start`` to its ``-done``. An event's name is the
whole HLO instruction; :func:`op_name` keeps what stands before `` = ``.
Host threads are the lines of ``/host:CPU``.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"   # copies and collectives in flight beside the ops
# the HLO opcode, which stands right before its operands' bracket; a psum
# lowers to an instruction NAMED psum.N whose opcode is all-reduce, and a
# fusion may merely take an %all-reduce.N as an operand
COLLECTIVE = re.compile(
    r"(?:^|[\s)])(?:all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(?:-start|-done)?\(")


def newest_xplane(logdir):
    files = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(files, key=os.path.getmtime)


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def union(intervals):
    """Merged, sorted intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def length(intervals):
    return sum(b - a for a, b in intervals)


def subtract(intervals, cover):
    """The parts of merged ``intervals`` that merged ``cover`` does not hide."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k, at = j, a
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > at:
                out.append([at, cover[k][0]])
            at = max(at, cover[k][1])
            k += 1
        if at < b:
            out.append([at, b])
    return out


def op_name(event_name):
    """``%fusion.263 = (bf16[2048]...) fusion(...)`` -> ``fusion.263``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def is_collective(event_name):
    return bool(COLLECTIVE.search(event_name.split(" = ", 1)[-1]))


def device_ops(profile, line_name=OPS_LINE):
    """{plane name: [(start_ns, end_ns, op name, is a collective), ...]} for
    every TPU."""
    out = {}
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == line_name:
                out[plane.name] = [
                    (e.start_ns, e.start_ns + e.duration_ns, op_name(e.name),
                     is_collective(e.name)) for e in line.events]
    return out


def host_events(profile):
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
    return out


def innermost(a, b, host):
    """What the host was doing in the gap ``[a, b)``: of the host events that
    overlap it, one that encloses no other of them — a span around the whole
    step (``bench_step``) overlaps every gap and names none — and of those
    the one that overlaps it longest."""
    over = [(min(b, hb) - max(a, ha), ha, hb, name) for ha, hb, name in host
            if min(b, hb) > max(a, ha)]
    leaves = [e for e in over if not any(
        e[1] <= ha and hb <= e[2] and hb - ha < e[2] - e[1] for _, ha, hb, _ in over)]
    return max(leaves)[3] if leaves else "host not traced"


def reduce(profile, top=10):
    """Seconds, averaged over the chips in the trace."""
    per_chip = device_ops(profile)
    if not per_chip:
        return {"chips": 0, "busy_s": 0.0, "ops_s": {}, "collective_s": 0.0,
                "exposed_collective_s": 0.0, "device_ops": [], "idle_gaps": []}
    n = len(per_chip)
    in_flight = device_ops(profile, ASYNC_LINE)
    busy = collective = exposed = 0.0
    by_name = {}
    gaps = []
    for chip, ops in per_chip.items():
        merged = union((a, b) for a, b, _, _ in ops)
        busy += length(merged)
        comm = union((a, b) for a, b, _, c in ops + in_flight.get(chip, []) if c)
        compute = union((a, b) for a, b, _, c in ops if not c)
        collective += length(comm)
        exposed += length(subtract(comm, compute))
        for a, b, name, _ in ops:
            by_name[name] = by_name.get(name, 0.0) + (b - a)
        gaps += [(merged[i + 1][0] - merged[i][1], merged[i][1], merged[i + 1][0])
                 for i in range(len(merged) - 1)]
    ops_s = {name: ns / n / 1e9 for name, ns in by_name.items()}
    host = host_events(profile)
    named = [[innermost(a, b, host), size / 1e9] for size, a, b in sorted(gaps, reverse=True)[:top]]
    return {
        "chips": n, "busy_s": busy / n / 1e9, "ops_s": ops_s,
        "collective_s": collective / n / 1e9,
        "exposed_collective_s": exposed / n / 1e9,
        "device_ops": [[name, s] for name, s in
                       sorted(ops_s.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": named,
    }
