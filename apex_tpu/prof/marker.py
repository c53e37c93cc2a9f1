"""Scope annotation + trace capture (pyprof.nvtx analog)."""

from __future__ import annotations

import contextlib
import functools
from typing import Callable, Iterable, Optional

import jax


def annotate(name: Optional[str] = None) -> Callable:
    """Decorator wrapping a function in ``jax.named_scope`` — the marker the
    reference pushes via NVTX around every patched call
    (``nvmarker.py:1-45``); the scope name (with arg shapes appended at
    trace time by XLA metadata) shows up in the profiler UI."""

    def deco(fn):
        scope = name or getattr(fn, "__qualname__", fn.__name__)

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with jax.named_scope(scope):
                return fn(*args, **kwargs)

        return wrapped

    return deco


def init(module, names: Optional[Iterable[str]] = None) -> None:
    """Wrap the named (or all public) functions of ``module`` with
    :func:`annotate` — the opt-in analog of pyprof's wrap-the-world
    ``nvtx.init()`` (``apex/pyprof/__init__.py:1-5``); explicit rather than
    interpreter-wide patching."""
    if names is None:
        names = [n for n in dir(module) if not n.startswith("_")
                 and callable(getattr(module, n))]
    for n in names:
        fn = getattr(module, n)
        if callable(fn):
            setattr(module, n, annotate(f"{module.__name__}.{n}")(fn))


@contextlib.contextmanager
def trace(log_dir: str, *, host_tracer_level: int = 2):
    """Capture a profiler trace to ``log_dir`` (an ``.xplane.pb`` viewable
    in TensorBoard/XProf) — replaces running under nvprof/nsys. At
    ``host_tracer_level`` 1 and above the host lines hold every
    ``monitor.span`` entered outside a JAX trace, on the profiler's clock
    beside the device's ``XLA Ops``."""
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = host_tracer_level
    jax.profiler.start_trace(log_dir, create_perfetto_link=False,
                             profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
