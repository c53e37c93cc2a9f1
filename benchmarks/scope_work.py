"""Device time by the program's own spans, for the per-layer readers.

``run["trace"]["ops_s"]`` is seconds by HLO instruction name; the span each
name was traced under stands in the optimized module of the step executable,
which is still alive in this process while the readers run.
``apex_tpu.prof.scopes`` finds that module among the client's live
executables, reads ``{instruction name: op_name}`` off its text and rolls
the seconds up by span; the readers of the block-level metrics
(``attn_block_ms``, ``moe_route_ms``, ``optimizer_ms`` ...) are each a call of
:func:`scope_ms`. The table is computed once a run and kept on ``run``; a
test hands its own as ``run["scope_table"]``. A program from before the
rollup (no ``apex_tpu.prof.scopes``) has nothing to read: every reader
returns ``None``.
"""
import time

try:
    from apex_tpu.prof import scopes
except ImportError:                       # the program has no rollup yet
    scopes = None

NO_SCOPE = getattr(scopes, "NO_SCOPE", None)     # the rollup's name for "under no span"


def rollup(run):
    """``apex_tpu.prof.scopes.rollup`` of the traced steps, ms a step, mean
    over chips; ``None`` without a device trace or without a scope table.
    Printed once, as the operator's command prints it."""
    if "scope_rollup" not in run:
        run["scope_rollup"] = _rollup(run)
    return run["scope_rollup"]


def _rollup(run):
    trace = run.get("trace")
    if scopes is None or not trace or not trace.get("chips") or not run.get("step_s"):
        return None
    t0 = time.perf_counter()
    table = run.get("scope_table") or scopes.live_scope_table(trace["ops_s"])
    if table is None:
        return None
    rolled = scopes.rollup(trace["ops_s"], table, len(run["step_s"]))
    known = sum(s for name, s in trace["ops_s"].items() if name in table)
    print(f"scopes: the step's module names {len(table)} instructions and covers "
          f"{100 * known / max(sum(trace['ops_s'].values()), 1e-30):.2f} % of the traced "
          f"operations' time; table and rollup took {time.perf_counter() - t0:.2f} s; "
          f"ms a traced step, mean over chips:\n{scopes.format_rollup(rolled)}", flush=True)
    return rolled


def scope_ms(run, spans, minus=()):
    """Milliseconds a traced step inside any of ``spans`` (child spans
    included), less the operations there whose name holds a part in
    ``minus``; ``None`` without a trace, without a table, or where no
    operation ran inside them."""
    rolled = rollup(run)
    return None if rolled is None else scopes.span_ms(rolled, spans, minus)


def phase_ms(run, phase):
    """Milliseconds a traced step of the operations of one phase (``fwd``,
    ``recompute``, ``bwd``, ``update``); ``None`` where there are none."""
    rolled = rollup(run)
    return (rolled["phases"][phase] or None) if rolled else None
