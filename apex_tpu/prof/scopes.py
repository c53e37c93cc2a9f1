"""Device time by the program's own spans.

Under a JAX trace a :class:`apex_tpu.monitor.span` is a ``jax.named_scope``,
so the path of nested spans stands in the metadata of every HLO instruction
(``op_name="jit(run)/amp/fwd_bwd/transpose(jvp(hybrid/attn))/mix/proj_in/
dot_general"``), and the profiler copies that string onto every executed
operation as its ``tf_op`` stat. This module joins the two halves — seconds
by instruction name from a device trace, the span path of each name — and
rolls the seconds up by span. The path of a name comes from either of two
sources, and both feed the one reduction, :func:`rollup`:

* the optimized module of the step executable, alive in the process that
  traced it (:func:`live_scope_table` finds it among the client's live
  executables; :func:`scope_table` reads its text);
* the raw ``.xplane.pb`` of a profiler run (:func:`xplane_ops`), which is
  what ``python -m apex_tpu.prof <logdir>`` prints from.

A span's **total** is the time of every operation traced inside it, its
**self** time that of the operations that sit in none of its child spans.
Every operation has one **phase**, by :func:`parse_path`'s rule.
"""

from __future__ import annotations

import glob
import importlib.util
import logging
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, Mapping, Optional, Tuple

logger = logging.getLogger(__name__)

# Every span the program enters under a trace, as the pair of path segments
# it leaves in ``op_name``, with what it holds. One table: the tests walk the
# program's sources and fail on a span that is not here.
SPANS: Dict[str, str] = {
    "amp/fwd_bwd": "amp.scaled_value_and_grad: the loss and its gradients",
    "amp/unscale_check": "amp.scaled_value_and_grad: unscale, the finite check",
    "amp/apply_master": "amp.apply_updates_with_master (XLA fuses the optimizer's "
                        "arithmetic into it: read it with <optimizer>/update)",
    "fused_adam/update": "optimizers/_fused.py", "fused_lamb/update": "optimizers/_fused.py",
    "fused_sgd/update": "optimizers/_fused.py", "fused_novograd/update": "optimizers/_fused.py",
    "fused_adagrad/update": "optimizers/_fused.py",
    "ddp/allreduce": "parallel.all_reduce_gradients",
    "gpt/embed": "models/gpt.py", "gpt/attn": "models/gpt.py _block",
    "gpt/mlp": "models/gpt.py _block", "gpt/unembed_xent": "models/gpt.py loss_fn",
    "hybrid/embed": "models/hybrid_decoder.py",
    "hybrid/gdn": "a delta-rule mixer half", "hybrid/attn": "a full-attention mixer half",
    "hybrid/kda": "a delta-rule mixer half whose decay is a vector a key channel",
    "hybrid/attn_win": "a windowed-attention mixer half",
    "hybrid/attn_mla": "a latent-attention mixer half",
    "hybrid/ssm": "a state-space (Mamba-2) mixer half",
    "hybrid/dense": "a dense feed-forward half", "hybrid/moe": "an expert half",
    "hybrid/unembed_xent": "models/hybrid_decoder.py loss_fn (a looped stack: every exit's)",
    "hybrid/exit": "a looped stack's exit gate, the exit distribution and the weighting",
    "moe/route": "transformer/moe.py dropless_moe_layer: scores, top-k, the plan",
    "moe/experts": "the grouped products", "moe/shared": "the shared experts",
    "mla/down": "_latent_mixer's proj_in: the query and down projections, the latent's norm",
    "mla/up": "_latent_mixer's proj_in: W_kvb",
    "mix/proj_in": "a mixer's input projections (w_q, w_k, w_v; w_qkvz, w_ba; w_in; "
                   "w_qkv, w_f, w_g, w_b)",
    "mix/place": "what stands between the projections and the kernel and after it: "
                 "per-head norms, rotary, the gates, beta / g",
    "mix/proj_out": "a mixer's output projection (w_o)",
}
PHASES = ("fwd", "recompute", "bwd", "update")
STEP_SPAN = "amp/fwd_bwd"     # an operation outside it is the update's
COVER = 0.9                   # of the traced seconds, for a module to be the one that ran them
NO_SCOPE = "(no scope)"

_JIT = re.compile(r"\bp?jit\([^()]*\)")
_WRAPPER = re.compile(r"[\w\-.]+\(|\)")    # jvp( transpose( vmap( custom_vjp_call( ... and )
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?\bop_name="([^"]*)"')
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_NUMBERED = re.compile(r"\.\d+(?:\..*)?$")


def parse_path(op_name: str) -> Tuple[Tuple[str, ...], str]:
    """``(spans, phase)`` of an instruction's ``op_name``.

    JAX's transform wrappers are stripped (``jit(run)``, ``jvp(hybrid/attn)``,
    ``transpose(jvp(...))``: what they wrap stays), and what is left is cut at
    ``/``; a pair of neighbouring segments that is a key of :data:`SPANS` is a
    span, in nesting order, each once (``transpose(jvp(amp/fwd_bwd))`` repeats
    the span it stands in). Whole segments: ``hybrid/attn_win`` is never
    ``hybrid/attn``.

    The phase: ``recompute`` where ``rematted_computation`` is a segment (the
    forward pass run again inside the backward pass of a ``jax.checkpoint``);
    else ``bwd`` where the path holds ``transpose(``; else ``fwd``; and
    ``update`` for a path outside ``amp/fwd_bwd``, whatever it holds.
    """
    segments = [s for s in _WRAPPER.sub("", _JIT.sub("", op_name)).split("/") if s]
    spans, i = [], 0
    while i < len(segments) - 1:
        pair = segments[i] + "/" + segments[i + 1]
        if pair in SPANS:
            if pair not in spans:
                spans.append(pair)
            i += 2
        else:
            i += 1
    if STEP_SPAN not in spans:
        phase = "update"
    elif "rematted_computation" in segments:
        phase = "recompute"
    elif "transpose(" in op_name:
        phase = "bwd"
    else:
        phase = "fwd"
    return tuple(spans), phase


def family(name: str) -> str:
    """``flash_bwd_bshd_fused.1`` -> ``flash_bwd_bshd_fused``; a clone's
    suffixes go with the number (``select_n.1368.clone.1`` -> ``select_n``),
    but a fusion XLA rematerialised on its own keeps that mark
    (``fusion.437.remat`` -> ``fusion.remat``)."""
    head, cut = _NUMBERED.subn("", name)
    return head + ".remat" if cut and ".remat" in name else head


def scope_table(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` for every instruction of every
    computation of an optimized module's text (``""`` where an instruction
    carries no metadata). Names are unique in a module; a fusion carries its
    root's path, as the profiler's ``tf_op`` does."""
    table = {}
    for line in hlo_text.splitlines():
        head = _INSTRUCTION.match(line)
        if head:
            path = _OP_NAME.search(line, head.end())
            table[head.group(1)] = path.group(1) if path else ""
    return table


def _module_texts() -> Iterable[str]:
    """The optimized modules of every executable alive on the local devices'
    clients, as text with nothing but what :func:`scope_table` reads."""
    import jax
    from jax._src.lib import _jax

    options = _jax.HloPrintOptions.short_parsable()
    options.print_metadata = True
    options.print_backend_config = False     # a Mosaic call's payload is megabytes
    options.print_large_constants = False
    options.print_operand_shape = False
    options.print_result_shape = False
    for client in {d.client for d in jax.local_devices()}:
        for executable in client.live_executables():
            try:
                modules = executable.hlo_modules()
            except RuntimeError:            # an executable the client has unloaded
                continue
            for module in modules:
                yield module.to_string(options)


def live_scope_table(ops_s: Mapping[str, float]) -> Optional[Dict[str, str]]:
    """The scope table of the live executable that ran ``ops_s``
    (``{instruction name: seconds}`` from a device trace): the module whose
    instruction names cover the most of those seconds — ``fusion.12`` stands
    in many modules, the step wins by weight. ``None``, and one logged line,
    where no module covers :data:`COVER` of them."""
    total = sum(ops_s.values())
    best, covered = None, 0.0
    for text in _module_texts():
        table = scope_table(text)
        got = sum(s for name, s in ops_s.items() if name in table)
        if got > covered:
            best, covered = table, got
    if best is None or covered < COVER * total:
        logger.warning("no live executable covers %.0f %% of the traced operations' time "
                       "(the best %.1f %%): no scope table", 100 * COVER,
                       100 * covered / total if total else 0.0)
        return None
    return best


def rollup(ops_s: Mapping[str, float], table: Mapping[str, str], steps: int = 1) -> dict:
    """Milliseconds a step by span, from seconds by instruction name and the
    ``op_name`` of each name.

    ``spans`` maps a span path (nested spans joined by ``/``, outermost first:
    ``amp/fwd_bwd/hybrid/moe/moe/route``) to ``{"total_ms": {phase: ms},
    "self_ms": {phase: ms}}``; ``ops`` maps a span path to ``{name family:
    ms}`` for the operations whose innermost span it is (the kernels among
    them under their own names, XLA's as ``fusion``, ``copy``, ...), and
    :data:`NO_SCOPE` to those under none of the program's spans, a name the
    table lacks among them; ``phases`` is every operation by phase;
    ``busy_ms`` their sum. The self times and ``ops[NO_SCOPE]`` add up to
    ``busy_ms``.
    """
    steps = max(int(steps), 1)
    total = defaultdict(lambda: dict.fromkeys(PHASES, 0.0))
    own = defaultdict(lambda: dict.fromkeys(PHASES, 0.0))
    ops = defaultdict(lambda: defaultdict(float))
    phases = dict.fromkeys(PHASES, 0.0)
    for name, seconds in ops_s.items():
        spans, phase = parse_path(table.get(name, ""))
        ms = 1e3 * seconds / steps
        phases[phase] += ms
        for depth in range(1, len(spans) + 1):
            total["/".join(spans[:depth])][phase] += ms
        inner = "/".join(spans)
        if spans:
            own[inner][phase] += ms
        ops[inner or NO_SCOPE][family(name)] += ms
    ops.setdefault(NO_SCOPE, {})
    return {
        "steps": steps,
        "busy_ms": sum(phases.values()),
        "phases": phases,
        "spans": {path: {"total_ms": total[path],
                         "self_ms": own.get(path, dict.fromkeys(PHASES, 0.0))}
                  for path in sorted(total)},
        "ops": {path: dict(sorted(by_family.items(), key=lambda kv: -kv[1]))
                for path, by_family in ops.items()},
    }


def inside(path: str, span: str) -> bool:
    """Whether the span path passes through ``span`` (whole segments)."""
    return f"/{span}/" in f"/{path}/"


def span_ms(rolled: dict, spans: Iterable[str], minus: Iterable[str] = ()) -> Optional[float]:
    """The time inside any of ``spans`` (each operation once, child spans
    included), less the operations there whose name holds a part in
    ``minus``; ``None`` where no operation ran inside them."""
    spans, minus = tuple(spans), tuple(minus)
    found = [ms for path, by_family in rolled["ops"].items()
             if any(inside(path, s) for s in spans)
             for name, ms in by_family.items() if not any(part in name for part in minus)]
    return sum(found) if found else None


# --- the raw .xplane.pb -------------------------------------------------------

def xplane_schema():
    """TensorFlow's copy of the ``.xplane.pb`` schema, loaded by file path:
    it needs protobuf alone (importing ``tensorflow`` for it takes 10 s), and
    it is the only copy this installation has."""
    package = importlib.util.find_spec("tensorflow")
    if package is None:
        raise FileNotFoundError("no tensorflow here to take the .xplane.pb schema from")
    path = os.path.join(package.submodule_search_locations[0],
                        "tsl", "profiler", "protobuf", "xplane_pb2.py")
    spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_xplane(path: str) -> str:
    """The ``.xplane.pb`` itself, or the newest one of a profiler logdir."""
    if os.path.isfile(path):
        return path
    pattern = os.path.join(path, "plugins", "profile", "*", "*.xplane.pb")
    files = glob.glob(pattern)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb (searched {pattern!r})")
    return max(files, key=os.path.getmtime)


def tf_ops(plane) -> Dict[int, str]:
    """``metadata_id`` -> the ``op_name`` in the operation's ``tf_op`` stat,
    which reads ``<op_name>:<op type>`` (the chip writes ``.../dot_general:``).
    A stat holds its string itself or, where the profiler interned it, the id
    of a stat metadata whose name is the string."""
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    out = {}
    for key, meta in plane.event_metadata.items():
        for stat in meta.stats:
            if stat_names.get(stat.metadata_id) == "tf_op":
                path = stat.str_value or stat_names.get(stat.ref_value, "")
                head, colon, _ = path.rpartition(":")
                out[key] = head if colon else path
    return out


def xplane_ops(space) -> Tuple[Dict[str, float], Dict[str, str], int]:
    """``(ops_s, table, chips)`` of a parsed ``XSpace``: seconds by
    instruction name on the ``XLA Ops`` line, mean over the TPU planes, and
    the ``tf_op`` of each name."""
    seconds, table, chips = defaultdict(float), {}, 0
    for plane in space.planes:
        if not _DEVICE.match(plane.name):
            continue
        chips += 1
        paths = tf_ops(plane)
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for event in line.events:
                name = plane.event_metadata[event.metadata_id].name
                name = name.split(" = ", 1)[0].lstrip("%")
                seconds[name] += event.duration_ps * 1e-12
                table.setdefault(name, paths.get(event.metadata_id, ""))
    return {k: v / max(chips, 1) for k, v in seconds.items()}, table, chips


def read_xplane(path: str):
    """:func:`xplane_ops` of a ``.xplane.pb`` file or a profiler logdir."""
    space = xplane_schema().XSpace()
    with open(find_xplane(path), "rb") as f:
        space.ParseFromString(f.read())
    return xplane_ops(space)


# --- the report ---------------------------------------------------------------

def format_rollup(rolled: dict, top: int = 4) -> str:
    """The rollup as a table: a row a span path, nested spans indented; ms a
    step in all and in the span's own operations, then by phase; under a span
    the ``top`` largest families of its own operations."""
    rows = [f"{'span':44s} {'total':>9s} {'self':>9s} " + " ".join(f"{p:>9s}" for p in PHASES)]
    line = lambda label, *v: f"{label:44s} " + " ".join(f"{x:9.3f}" for x in v)  # noqa: E731

    def own_ops(path, indent):
        for name, ms in list(rolled["ops"].get(path, {}).items())[:top]:
            rows.append(f"{indent}:: {name}".ljust(55) + f"{ms:9.3f}")

    for path, row in rolled["spans"].items():
        depth = len(path.split("/")) // 2
        rows.append(line("  " * (depth - 1) + "/".join(path.split("/")[-2:]),
                         sum(row["total_ms"].values()), sum(row["self_ms"].values()),
                         *(row["total_ms"][p] for p in PHASES)))
        own_ops(path, "  " * depth)
    bare = sum(rolled["ops"][NO_SCOPE].values())
    rows.append(line(NO_SCOPE, bare, bare))
    own_ops(NO_SCOPE, "  ")
    rows.append(line("busy", rolled["busy_ms"], rolled["busy_ms"],
                     *(rolled["phases"][p] for p in PHASES)))
    return "\n".join(rows)


def format_xplane_report(path: str, steps: int = 1, top: int = 4) -> str:
    """What ``python -m apex_tpu.prof`` prints from a raw ``.xplane.pb``."""
    path = find_xplane(path)
    ops_s, table, chips = read_xplane(path)
    if not chips:
        raise FileNotFoundError(f"no /device:TPU:<n> plane in {path!r}")
    head = (f"{path}: {chips} chip(s), device time by the program's spans, "
            f"ms a step over {max(int(steps), 1)} step(s), mean over chips")
    return head + "\n" + format_rollup(rollup(ops_s, table, steps), top)
