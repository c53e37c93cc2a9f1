"""Step-anatomy span instrumentation: host enter/exit timestamps + the
named-scope join key into device traces.

The profiler layer (:mod:`apex_tpu.prof`) can read a ``jax.profiler``
trace and the monitor can time whole steps, but neither can say *which
part* of a step a device kernel belongs to — the reference's pyprof
solves this with NVTX ranges joined to kernels through the nvprof
database (``apex/pyprof/parse/db.py``). On TPU the join comes free:
``jax.named_scope`` names entered while JAX **traces** ride into every
HLO's name in the device trace. A :func:`span` therefore does double
duty:

* **host side** — when monitoring is enabled, it records a monotonic-ns
  enter/exit pair and emits one ``span`` record (rank-tagged, riding the
  same JSONL stream as step records) with any caller attrs
  (``bytes=``, ``axis=``, ``coll=`` for collectives);
* **device side** — it enters ``jax.named_scope(name)``, so any op
  traced inside carries the span's **path** (nested spans join with
  ``/``) as a prefix of its trace name. ``prof.trace_reader.correlate``
  joins the two halves on exactly that prefix.

Spans in *traced* code (pipeline ticks, TP boundary collectives, the
collective-matmul rings, decode blocks) run their Python once per trace:
their host duration is tracing time, not execution time, so the record
carries ``traced: true`` and consumers use them for the scope path and
attrs only — the real durations come from the device events under the
scope. Host-phase spans (``step``, the profile bench's timed passes)
carry wall time the anatomy table can trust.

Disabled cost: one registry load + ``is None`` test, then a bare
``yield`` — no jax import, no named_scope, no clock read (the same
contract as every other monitor hook). This also means scope names only
reach the device trace when monitoring was enabled at *trace* time:
enable the monitor before compiling the step you want to attribute
(``bench.py --profile`` does).
"""

from __future__ import annotations

import contextlib
from typing import Optional

from apex_tpu.monitor import registry as _reg
# THE unified clock (trace.monotonic_ns == time.perf_counter_ns): span
# t0_ns, registry t_ns and the serve clock all share its CLOCK_MONOTONIC
# base, so `monitor trace` merges the streams without skew
from apex_tpu.monitor.trace import monotonic_ns

# the active span path, innermost last. Training loops and tracing are
# single-threaded per process; a plain list keeps the enabled fast path
# at two list ops per span.
_STACK: list = []


def span_path() -> str:
    """The current span path ("" at top level) — the prefix any op traced
    right now would carry in a device trace."""
    return "/".join(_STACK)


@contextlib.contextmanager
def span(name: str, **attrs):
    """Instrument a region: ``with span("fwd_bwd"): ...``.

    Emits one ``span`` record on exit — ``name`` is the full ``/``-joined
    path of nested spans, ``t0_ns``/``dur_ns`` the monotonic host window,
    ``traced: true`` when entered under a JAX trace (host times then
    measure tracing, not execution) — and wraps the body in
    ``jax.named_scope(name)`` so traced ops join back to this span by
    name prefix. ``attrs`` pass through to the record (collective spans
    carry ``coll=kind, axis=..., bytes=...`` — what the CostDB
    calibration prices). No-op while monitoring is disabled.
    """
    r = _reg.get_registry()
    if r is None:
        yield
        return
    import jax

    _STACK.append(name)
    path = "/".join(_STACK)
    traced = not jax.core.trace_ctx.is_top_level()
    t0 = monotonic_ns()
    try:
        with jax.named_scope(name):
            yield
    finally:
        dur = monotonic_ns() - t0
        _STACK.pop()
        # the registry may have been torn down inside the body
        r = _reg.get_registry()
        if r is not None:
            if traced:
                attrs.setdefault("traced", True)
            r.emit("span", name=path, t0_ns=t0, dur_ns=dur, **attrs)


@contextlib.contextmanager
def collective_span(kind: str, payload, axis_name: Optional[str]):
    """A :func:`span` around one collective, carrying the calibration
    attrs (``coll``, ``axis``, ``bytes`` — payload size from static
    shapes, the same accounting as ``hooks.count_collective``). The span
    segment is ``{kind}_{axis}`` so distinct axes keep distinct scope
    paths in the device trace. No-op while disabled; identity when
    ``axis_name`` is None (tp=1 fallthrough paths)."""
    if axis_name is None or _reg.get_registry() is None:
        yield
        return
    from apex_tpu.monitor.hooks import tree_bytes

    with span(f"{kind}_{axis_name}", coll=kind, axis=axis_name,
              bytes=tree_bytes(payload)):
        yield
