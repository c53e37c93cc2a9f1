"""Step-anatomy span instrumentation: one name, entered once, read in
three places.

The reference's pyprof joins NVTX ranges to kernels through the nvprof
database (``apex/pyprof/parse/db.py``). Here the join needs no database. A
:func:`span` always enters the one of two names that can be read where it
stands, both free when nobody is looking, and writes a record while the
monitor is on:

* **scope** — under a JAX trace it enters ``jax.named_scope(name)``,
  registry or no registry, so whatever JAX traces inside carries the
  span's **path** (nested spans join with ``/``) in its HLO metadata:
  XProf's op view shows ``gpt/attn`` or ``amp/apply_master`` beside
  ``fusion.263``. The cost is trace time only, and a program compiled with
  the monitor enabled is the same program as one compiled without.
* **profiler annotation** — outside a JAX trace (a *host-phase* span:
  ``serve_decode``, ``decode_step``, a training loop's ``step``) it enters
  ``jax.profiler.TraceAnnotation(path)`` instead, so the span lands on a
  ``/host:CPU`` line of the profiler's own ``.xplane.pb``, on the
  profiler's clock, next to the device's ``XLA Ops``. With no profiler
  session the annotation is a flag test in C++. A scope would be lost
  there (``jit`` starts every trace from an empty name stack, so a scope
  entered around a dispatch reaches no HLO), and under a trace an
  annotation would time tracing: each is entered where it can be read.
* **record** — while a monitor registry is enabled it reads the monotonic
  clock on enter and exit and emits one ``span`` record (rank-tagged,
  riding the same JSONL stream as step records) with any caller attrs
  (``bytes=``, ``axis=``, ``coll=`` for collectives). A span entered under
  a JAX trace runs its Python once per trace, so its record carries
  ``traced: true`` and consumers use it for the path and attrs only.

With no registry and no profiler session a host-phase span reads no clock
and writes nothing; what remains is the annotation's flag test and two list
operations, under two microseconds of host time (measured in ``PERF.md``).
Spans in traced code cost nothing at run time.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax

from apex_tpu.monitor import registry as _reg
# THE unified clock (trace.monotonic_ns == time.perf_counter_ns): span
# t0_ns, registry t_ns and the serve clock all share its CLOCK_MONOTONIC
# base, so `monitor trace` merges the streams without skew
from apex_tpu.monitor.trace import monotonic_ns


class _Stack(threading.local):
    """The active span path of this thread, innermost last. A serve loop
    and a background thread each nest their own spans."""

    def __init__(self):
        self.names: list = []


_STACK = _Stack()


def span_path() -> str:
    """The current span path ("" at top level) — the prefix any op traced
    right now would carry in a device trace."""
    return "/".join(_STACK.names)


class span:
    """Instrument a region: ``with span("fwd_bwd"): ...``.

    Wraps the body in ``jax.named_scope(name)`` under a JAX trace and in
    ``jax.profiler.TraceAnnotation(path)`` outside one. While a monitor
    registry is enabled it also emits one ``span`` record on exit —
    ``name`` is the full ``/``-joined path of nested spans,
    ``t0_ns``/``dur_ns`` the monotonic host window, ``traced: true`` when
    entered under a JAX trace (host times then measure tracing, not
    execution). ``attrs`` pass through to the record (collective spans
    carry ``coll=kind, axis=..., bytes=...`` — what the CostDB calibration
    prices).

    A class and not a ``contextlib.contextmanager`` generator: the
    generator's own machinery was half of a host-phase span's cost.
    """

    __slots__ = ("name", "attrs", "_path", "_traced", "_t0", "_inner")

    def __init__(self, name: str, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        names = _STACK.names
        self._path = path = "/".join(names + [self.name])
        self._traced = traced = not jax.core.trace_ctx.is_top_level()
        self._t0 = monotonic_ns() if _reg.get_registry() is not None else None
        self._inner = (jax.named_scope(self.name) if traced
                       else jax.profiler.TraceAnnotation(path))
        self._inner.__enter__()
        names.append(self.name)

    def __exit__(self, *exc):
        try:
            return self._inner.__exit__(*exc)
        finally:
            _STACK.names.pop()
            # the registry may have been torn down (or set up) inside the body
            r = None if self._t0 is None else _reg.get_registry()
            if r is not None:
                if self._traced:
                    self.attrs.setdefault("traced", True)
                r.emit("span", name=self._path, t0_ns=self._t0,
                       dur_ns=monotonic_ns() - self._t0, **self.attrs)


@contextlib.contextmanager
def collective_span(kind: str, payload, axis_name: Optional[str]):
    """A :func:`span` around one collective, carrying the calibration
    attrs (``coll``, ``axis``, ``bytes`` — payload size from static
    shapes, the same accounting as ``hooks.count_collective``). The span
    segment is ``{kind}_{axis}`` so distinct axes keep distinct scope
    paths in the device trace. Identity when ``axis_name`` is None (tp=1
    fallthrough paths)."""
    if axis_name is None:
        yield
        return
    from apex_tpu.monitor.hooks import tree_bytes

    with span(f"{kind}_{axis_name}", coll=kind, axis=axis_name,
              bytes=tree_bytes(payload)):
        yield
