"""jaxpr-level static analysis: the recursive walker and static cost
extraction the JXP contracts (:mod:`apex_tpu.lint.contracts`) and the
planner's predicted-cost substrate share.

Why a jaxpr walker next to the AST linter: apexlint (APX rules) sees
source text — it can say "this *call* looks like it materializes a bias"
but not "the traced program *contains* an ``(h, sq, sk)`` intermediate".
The invariants this repo actually lives and dies by — no full-width
``all_gather`` on an overlapped ring, the zb schedule's third scan of
exactly ``M·v`` ticks, donation honored, no O(s²) bias aval — are
properties of the *jaxpr*, the program the compiler actually sees. Until
this module they were enforced by one-off duck-typed walkers scattered
through ``tests/test_pipeline.py``, ``tests/test_attention.py`` and
``tests/test_collective_matmul.py``; this is the one shared engine.

The same walk yields the planner's static cost model for free
(:func:`static_cost`): every collective eqn carries its payload aval and
axis, every ``dot_general`` its FLOPs, and enclosing ``scan`` lengths
give static execution counts — AMP-style plan search (arXiv:2210.07297)
prices candidate plans from exactly these numbers, and veScale
(arXiv:2509.07003) is the argument for deriving them from the traced
program rather than hand math.

Like the rest of the lint package this module imports NOTHING outside
the stdlib: jaxpr objects are walked duck-typed (``.eqns`` /
``.jaxpr`` / ``.primitive.name`` / ``.aval``), the same convention the
migrated test walkers used, so the analysis survives jax's core/extend
reshuffles and never imports the jax it is vetting. Callers hand in
whatever ``jax.make_jaxpr`` returned.

Walk model
----------
:func:`iter_sites` yields one :class:`EqnSite` per equation at every
nesting level, descending into EVERY sub-jaxpr found in ``eqn.params``
(jit's ``jaxpr``, scan's ``jaxpr``, while's ``cond_jaxpr``/
``body_jaxpr``, cond's ``branches``, custom_vjp/jvp's ``fun_jaxpr``/
``call_jaxpr``, shard_map's ``jaxpr``, remat, pallas_call — anything
Jaxpr-shaped, listed or bare). Each site carries:

* ``path`` — ``/``-joined segments of the higher-order eqns containing
  it (``"jit:step/scan:6"``); scan segments embed the static length,
  jit segments the wrapped function name, so contracts can target
  regions by regex (the zb dW sweep is ``scan:<M·v>``);
* ``mult`` — the product of enclosing scan lengths: the number of times
  the eqn executes per call of the traced program (the unit
  ``monitor.hooks.count_collective`` counts in);
* ``bounded`` — False under a ``while`` body, whose trip count is not
  static (cost rows fed from such sites are flagged, never silently
  priced).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, List, Optional, Tuple

#: jaxpr collective primitive name -> the counter kind
#: ``monitor.hooks.count_collective`` uses for the same traffic, so a
#: StaticCostReport's kind×axis keys join 1:1 against counted bytes and
#: the CostDB's calibrated rows.
COLLECTIVE_PRIMS = {
    "psum": "psum",
    "psum2": "psum",
    "all_gather": "all_gather",
    "all_gather_invariant": "all_gather",
    "reduce_scatter": "psum_scatter",
    "psum_scatter": "psum_scatter",
    "ppermute": "ppermute",
    "pshuffle": "ppermute",
    "all_to_all": "all_to_all",
}

#: primitives whose sub-jaxpr is a KERNEL body (VMEM tiles, priced by
#: measured kernel events, not the static walker) — the walker descends
#: for completeness but cost/aval accounting skips anything under them
_KERNEL_PRIMS = ("pallas_call",)


@dataclasses.dataclass(frozen=True)
class EqnSite:
    """One equation at one nesting level of a walked jaxpr."""
    path: str      #: containing higher-order path ("" = top level)
    eqn: Any       #: the JaxprEqn (duck-typed)
    mult: int      #: static executions per program call (scan lengths)
    bounded: bool  #: False when under a while body (unknown trip count)

    @property
    def prim(self) -> str:
        return self.eqn.primitive.name

    def under_kernel(self) -> bool:
        """True inside a Pallas kernel body: avals there are VMEM tiles,
        not HBM arrays — the O(s²) claims and the byte accounting are
        about what exists OUTSIDE kernels (kernel operands are checked
        at the pallas_call eqn itself, which is never under_kernel)."""
        return any(seg.split(":", 1)[0] in _KERNEL_PRIMS
                   for seg in self.path.split("/") if seg)


# --- duck-typed jaxpr plumbing -----------------------------------------------

def as_jaxpr(obj):
    """The raw Jaxpr behind a ClosedJaxpr / Jaxpr / anything wearing one.
    The ``.jaxpr`` unwrap is checked FIRST: a ClosedJaxpr proxies
    ``.eqns`` but not ``.outvars``, so the eqns check alone would hand
    callers a half-jaxpr."""
    inner = getattr(obj, "jaxpr", None)
    if hasattr(inner, "eqns"):
        return inner
    if hasattr(obj, "eqns"):
        return obj
    raise TypeError(
        f"not a jaxpr: {type(obj).__name__} (pass jax.make_jaxpr(fn)(*args) "
        "or its .jaxpr)")


def sub_jaxprs(val) -> Iterator[Any]:
    """Every Jaxpr nested in one ``eqn.params`` value — bare, closed, or
    inside a list/tuple (cond's ``branches``)."""
    if hasattr(getattr(val, "jaxpr", None), "eqns"):
        yield val.jaxpr
    elif hasattr(val, "eqns"):
        yield val
    elif isinstance(val, (list, tuple)):
        for item in val:
            yield from sub_jaxprs(item)


def _is_jit_eqn(eqn) -> bool:
    """True for the eqn a nested ``jax.jit`` call leaves in a jaxpr — the
    one carrying ``donated_invars``. jax 0.9 names the primitive ``jit``;
    the walker, the donation contracts and the liveness analysis all ask
    here, so the name lives in one place."""
    return eqn.primitive.name == "jit"


def _segment(eqn) -> str:
    """Path segment for one higher-order eqn: scans embed their static
    length (``scan:6`` — how contracts target the zb dW sweep), jit its
    wrapped-function name (``jit:train_step``)."""
    name = eqn.primitive.name
    if name == "scan":
        length = eqn.params.get("length")
        if isinstance(length, int):
            return f"scan:{length}"
    if _is_jit_eqn(eqn):
        fn_name = eqn.params.get("name")
        if isinstance(fn_name, str) and fn_name:
            return f"jit:{fn_name}"
    return name


def iter_sites(jaxpr_like, *, path: str = "", mult: int = 1,
               bounded: bool = True) -> Iterator[EqnSite]:
    """Yield an :class:`EqnSite` for every eqn at every nesting level."""
    j = as_jaxpr(jaxpr_like)
    for eqn in j.eqns:
        yield EqnSite(path, eqn, mult, bounded)
        subs: List[Any] = []
        for val in eqn.params.values():
            subs.extend(sub_jaxprs(val))
        if not subs:
            continue
        name = eqn.primitive.name
        child_mult, child_bounded = mult, bounded
        if name == "scan":
            length = eqn.params.get("length")
            if isinstance(length, int):
                child_mult = mult * length
        elif name == "while":
            child_bounded = False
        seg = _segment(eqn)
        for i, sub in enumerate(subs):
            child = f"{path}/{seg}" if path else seg
            if len(subs) > 1:
                child = f"{child}.{i}"
            yield from iter_sites(sub, path=child, mult=child_mult,
                                  bounded=child_bounded)


def iter_levels(jaxpr_like, *, path: str = "") -> Iterator[Tuple[str, Any]]:
    """Yield ``(path, jaxpr)`` for every nesting level — the per-level
    view the donation contracts need (use-after-donate is a statement
    about *later eqns of the same level*, which the flat site stream
    cannot express)."""
    j = as_jaxpr(jaxpr_like)
    yield path, j
    for eqn in j.eqns:
        subs: List[Any] = []
        for val in eqn.params.values():
            subs.extend(sub_jaxprs(val))
        if not subs:
            continue
        seg = _segment(eqn)
        for i, sub in enumerate(subs):
            child = f"{path}/{seg}" if path else seg
            if len(subs) > 1:
                child = f"{child}.{i}"
            yield from iter_levels(sub, path=child)


def scan_sites(jaxpr_like) -> List[EqnSite]:
    """Every ``scan`` eqn anywhere in the program (any nesting level)."""
    return [s for s in iter_sites(jaxpr_like) if s.prim == "scan"]


def scan_lengths(jaxpr_like) -> List[int]:
    """Every static scan length anywhere in the program — the trace-time
    geometry the pipeline schedules compile to (the former
    ``tests/test_pipeline.py`` helper, now shared)."""
    out = []
    for s in scan_sites(jaxpr_like):
        length = s.eqn.params.get("length")
        if isinstance(length, int):
            out.append(length)
    return out


# --- per-eqn accounting -------------------------------------------------------

def collective_kind(eqn) -> Optional[str]:
    """The hook-counter kind of a collective eqn, None for anything else."""
    return COLLECTIVE_PRIMS.get(eqn.primitive.name)


def collective_axes(eqn) -> Tuple[str, ...]:
    """Mesh axis names a collective eqn rides (``axis_name`` or ``axes``
    param, normalized to a tuple of strings)."""
    params = eqn.params
    axes = params.get("axis_name", params.get("axes", ()))
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def aval_bytes(var) -> int:
    """Static byte size of one var's aval; 0 when not statically known
    (abstract tokens, polymorphic dims)."""
    aval = getattr(var, "aval", None)
    size = getattr(aval, "size", None)
    dtype = getattr(aval, "dtype", None)
    if size is None or dtype is None:
        return 0
    try:
        return int(size) * dtype.itemsize
    except TypeError:
        return 0


def eqn_input_bytes(eqn) -> int:
    """Payload bytes of one collective eqn: the sum of its operand avals
    — the same per-call accounting ``monitor.hooks.tree_bytes`` applies
    to the payload a ``count_traffic`` call site passes (a multi-leaf
    psum is one eqn with one invar per leaf)."""
    return sum(aval_bytes(v) for v in eqn.invars)


def dot_flops(eqn) -> float:
    """FLOPs of one ``dot_general``: ``2 · batch · m · n · k`` read off
    the operand avals and dimension numbers (the multiply-add convention
    XLA's ``model_flops`` uses, so static classes join the CostDB's
    measured GEMM classes)."""
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    a = getattr(eqn.invars[0], "aval", None)
    b = getattr(eqn.invars[1], "aval", None)
    ashape = getattr(a, "shape", None)
    bshape = getattr(b, "shape", None)
    if ashape is None or bshape is None:
        return 0.0
    k = _prod(ashape[i] for i in lc)
    batch = _prod(ashape[i] for i in lb)
    m = _prod(ashape[i] for i in range(len(ashape))
              if i not in lc and i not in lb)
    n = _prod(bshape[i] for i in range(len(bshape))
              if i not in rc and i not in rb)
    return 2.0 * batch * m * n * k


def _prod(it) -> int:
    out = 1
    for v in it:
        out *= int(v)
    return out


def pow2_floor(x: float) -> int:
    """Power-of-two floor (1 below 2) — the same bucket key
    ``prof.calibrate.size_bucket`` uses, duplicated here so the lint
    package stays stdlib-only (parity is pinned by
    ``tests/test_jaxpr_check.py::TestStaticCost::test_bucket_parity``)."""
    b = 1
    while b * 2 <= x:
        b *= 2
    return b


# --- StaticCostReport ---------------------------------------------------------

def _new_acc() -> Dict[str, Any]:
    return {"collectives": {}, "gemms": {}, "eqns": 0, "unbounded": 0}


def _merge_max(parent: Dict[str, Any], branches: List[Dict[str, Any]]
               ) -> None:
    """Fold cond-branch accumulators into the parent: exactly ONE branch
    executes per call, so branch costs are ALTERNATIVES — summing them
    would silently overstate every cond-bearing program. Per key the
    field-wise max over branches (the tightest per-key upper bound
    expressible without knowing the predicate) is reduced FIRST, then
    ADDED to the parent's running totals — the same key outside the
    cond is a separate execution, never absorbed by (or absorbing) the
    branch cost. eqns stay a walk statistic and sum."""
    for table in ("collectives", "gemms"):
        best: Dict[str, Dict[str, Any]] = {}
        for branch in branches:
            for key, ent in branch[table].items():
                dst = best.setdefault(key, {field: 0 for field in ent})
                for field, v in ent.items():
                    dst[field] = max(dst[field], v)
        for key, ent in best.items():
            dst = parent[table].setdefault(
                key, {field: 0 for field in ent})
            for field, v in ent.items():
                dst[field] += v
    parent["eqns"] += sum(b["eqns"] for b in branches)
    parent["unbounded"] += max((b["unbounded"] for b in branches),
                               default=0)


def _accumulate(jaxpr_like, mult: int, bounded: bool,
                acc: Dict[str, Any]) -> None:
    j = as_jaxpr(jaxpr_like)
    for eqn in j.eqns:
        acc["eqns"] += 1
        name = eqn.primitive.name
        kind = collective_kind(eqn)
        if kind is not None:
            axis = ",".join(collective_axes(eqn))
            key = f"{kind}[{axis}]"
            if not bounded:
                acc["unbounded"] += 1
            ent = acc["collectives"].setdefault(key,
                                                {"calls": 0, "bytes": 0})
            ent["calls"] += mult
            ent["bytes"] += eqn_input_bytes(eqn) * mult
        elif name == "dot_general":
            flops = dot_flops(eqn)
            if flops > 0:
                if not bounded:
                    acc["unbounded"] += 1
                key = f"flops_{pow2_floor(flops)}"
                ent = acc["gemms"].setdefault(key,
                                              {"calls": 0, "flops": 0.0})
                ent["calls"] += mult
                ent["flops"] += flops * mult
        if name in _KERNEL_PRIMS:
            continue  # kernel bodies: VMEM tiles, priced by measured events
        subs: List[Any] = []
        for val in eqn.params.values():
            subs.extend(sub_jaxprs(val))
        if not subs:
            continue
        if name == "cond" and len(subs) > 1:
            branch_accs = []
            for sub in subs:
                branch = _new_acc()
                _accumulate(sub, mult, bounded, branch)
                branch_accs.append(branch)
            _merge_max(acc, branch_accs)
            continue
        child_mult, child_bounded = mult, bounded
        if name == "scan":
            length = eqn.params.get("length")
            if isinstance(length, int):
                child_mult = mult * length
        elif name == "while":
            child_bounded = False
        for sub in subs:
            _accumulate(sub, child_mult, child_bounded, acc)


def static_cost(jaxpr_like, *, entrypoint: str = "") -> Dict[str, Any]:
    """Accumulate the walked program into a ``kind: "static_cost"``
    artifact: per-collective calls/bytes by ``<kind>[<axis>]`` and
    per-GEMM calls/FLOPs by power-of-two FLOPs class, every count
    multiplied by enclosing scan lengths (a ppermute inside the
    ``M·v + S − 1``-tick pipeline scan is that many executions per
    step).

    The kind×axis keys are exactly the ``monitor.hooks.count_collective``
    tags and the CostDB's collective keys; the GEMM class keys are
    ``prof.calibrate.gemm_samples``'s — so ``prof.calibrate
    .diff_static_cost`` can line predicted bytes/FLOPs up against
    calibrated rates with a plain dict join. Pallas kernel bodies are
    skipped (their operands are accounted at the ``pallas_call`` eqn's
    level; in-kernel FLOPs are priced by the CostDB's measured kernel
    events, which the static walker cannot see per-grid-point).
    Collectives under a ``while`` body are counted ONCE and tallied in
    ``unbounded_sites`` — a row fed by an unknown trip count must
    be flagged, not silently priced. ``cond`` branches are ALTERNATIVES
    (one executes per call): per key the report takes the field-wise max
    over branches rather than summing them.

    Schema: :data:`apex_tpu.monitor.schema.STATIC_COST_SCHEMA`, gated by
    ``tools/validate_metrics.py --static-cost``.
    """
    acc = _new_acc()
    _accumulate(jaxpr_like, 1, True, acc)
    from apex_tpu.monitor.registry import SCHEMA_VERSION

    collectives, gemms = acc["collectives"], acc["gemms"]
    return {
        "schema": SCHEMA_VERSION,
        "kind": "static_cost",
        "entrypoint": entrypoint,
        "collectives": {k: collectives[k] for k in sorted(collectives)},
        "gemms": {k: gemms[k] for k in sorted(gemms)},
        "total_collective_bytes": sum(e["bytes"]
                                      for e in collectives.values()),
        "total_gemm_flops": sum(e["flops"] for e in gemms.values()),
        "eqns": acc["eqns"],
        "unbounded_sites": acc["unbounded"],
    }
