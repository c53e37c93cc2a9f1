"""JXP contract library: reusable invariant checks over traced jaxprs.

Where apexlint's APX rules judge *source text*, a JXP contract judges the
*traced program* — the jaxpr the compiler actually sees. Each contract is
a small declarative object (code + human description + a check over the
shared :mod:`apex_tpu.lint.jaxpr_check` walk); an entrypoint in
:mod:`apex_tpu.lint.entrypoints` declares the set it must satisfy, and
the migrated test suites assert the same objects directly
(:func:`assert_contracts`) — one engine owns every jaxpr invariant that
used to live as a one-off duck-typed walker in a test file.

Code families (catalogue with bad/good traces: ``docs/api/lint.md``):

* **JXP1xx** program structure — :func:`scan_count` (JXP101),
  :func:`scan_length` (JXP102): the schedule-geometry witnesses (the zb
  dW sweep is "a third scan of exactly M·v ticks").
* **JXP2xx** donation — :func:`donation_honored` (JXP201: a buffer
  donated into a jit eqn is dead; reading it afterwards is
  use-after-free at the XLA level), :func:`donation_rebound` (JXP202: a
  donated operand with no same-aval output cannot have its buffer
  reused — the donation silently buys nothing).
* **JXP3xx** aval shape — :func:`no_aval_matching` (JXP301): no
  intermediate anywhere in the program matches a forbidden shape
  pattern (the bucketed-bias memory claim: no two >= seq dims).
* **JXP4xx** collective inventory — :func:`no_full_width_all_gather`
  (JXP401), :func:`ppermute_present` (JXP402),
  :func:`collective_free_region` (JXP403).
* **JXP5xx** precision — :func:`fp32_accumulation` (JXP501: a scan
  carry accumulated by add in bf16/fp16 loses mantissa every tick).
* **JXP6xx** static peak memory (apexmem) — :func:`peak_memory_bound`
  (JXP601: the donation-aware liveness peak of
  :func:`apex_tpu.lint.liveness.analyze` stays under a byte budget),
  :func:`donation_aliased` (JXP602: a donated buffer is provably
  counted once — the alias survives the liveness accounting, not just
  the JXP202 aval match).

Stdlib-only, like the rest of the package: contracts consume the
duck-typed walk, never jax itself.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, List, Optional, Sequence, Tuple

from apex_tpu.lint.jaxpr_check import (
    EqnSite,
    _is_jit_eqn,
    as_jaxpr,
    collective_axes,
    collective_kind,
    iter_levels,
    iter_sites,
    sub_jaxprs,
)

_LOW_PRECISION = ("bfloat16", "float16")
_ACCUM_PRIMS = ("add", "add_any")

#: the JXP contract catalogue: code -> (name, one-line summary). The
#: docs-catalogue test enforces a ``### JXPnnn`` entry with bad/good
#: trace snippets in docs/api/lint.md for every row, the same discipline
#: as the APX rule registry; ``--list-rules`` prints it after the AST
#: rules.
JXP_CODES = {
    "JXP101": ("scan-count",
               "the number of scan eqns anywhere in the program matches "
               "the declared count/bounds"),
    "JXP102": ("scan-length",
               "a scan of exactly N static ticks exists (or, forbidden, "
               "does not) — the schedule-geometry witness"),
    "JXP201": ("donation-use-after-donate",
               "no value read (or returned) after its buffer was donated "
               "into a jit call"),
    "JXP202": ("donated-not-rebound",
               "every donated operand has a same-aval output to rebind — "
               "a donation with no matching output buys nothing"),
    "JXP301": ("no-aval-matching",
               "no eqn operand/output matches a forbidden shape pattern "
               "(Pallas kernel bodies exempt — VMEM tiles, not HBM)"),
    "JXP401": ("no-full-width-all-gather",
               "no all_gather over the named axis anywhere in the "
               "program — the overlapped-ring acceptance"),
    "JXP402": ("ppermute-present",
               "at least one ppermute over the named axis — the ring / "
               "pipeline-hop witness"),
    "JXP403": ("collective-free-region",
               "no collective primitive under paths matching a regex "
               "(a region that matches nothing is itself a violation)"),
    "JXP501": ("fp32-accumulation",
               "no scan carry accumulated by add in bf16/fp16 — "
               "accumulate fp32, downcast once"),
    "JXP601": ("peak-memory-bound",
               "the donation-aware static liveness peak of the traced "
               "program stays under a byte budget"),
    "JXP602": ("donation-aliased",
               "the liveness analysis finds the named donated buffer "
               "really aliased input->output (counted once, not twice)"),
}


@dataclasses.dataclass(frozen=True)
class ContractFinding:
    code: str      #: JXPnnn
    contract: str  #: the contract instance's human label
    path: str      #: jaxpr path of the offending site ("" = whole program)
    message: str

    def render(self) -> str:
        where = self.path or "<top>"
        return f"{self.code} [{where}] {self.message}"


@dataclasses.dataclass(frozen=True)
class Contract:
    code: str
    name: str
    describe: str  #: instance description, parameters included
    check: Callable[["Walk"], List[ContractFinding]]


class Walk:
    """One materialized walk of a jaxpr, shared by every contract checked
    against it (the walker runs once, not once per contract)."""

    def __init__(self, jaxpr_like):
        self.jaxpr = as_jaxpr(jaxpr_like)
        self.sites: List[EqnSite] = list(iter_sites(self.jaxpr))

    def levels(self):
        return iter_levels(self.jaxpr)

    def scans(self) -> List[EqnSite]:
        return [s for s in self.sites if s.prim == "scan"]


def check_jaxpr(jaxpr_like, contracts: Sequence[Contract]
                ) -> List[ContractFinding]:
    """Check every contract against one traced program; returns the
    flattened findings (empty = all contracts hold)."""
    walk = jaxpr_like if isinstance(jaxpr_like, Walk) else Walk(jaxpr_like)
    findings: List[ContractFinding] = []
    for c in contracts:
        findings.extend(c.check(walk))
    return findings


def assert_contracts(jaxpr_like, contracts: Sequence[Contract]) -> None:
    """Raise ``AssertionError`` listing every violated contract — the
    drop-in replacement for the hand-rolled jaxpr asserts the test
    suites used to carry."""
    findings = check_jaxpr(jaxpr_like, contracts)
    if findings:
        raise AssertionError(
            "jaxpr contract violation(s):\n  "
            + "\n  ".join(f.render() for f in findings))


# --- JXP1xx: program structure ------------------------------------------------

def scan_count(expected: Optional[int] = None, *,
               min_count: Optional[int] = None,
               max_count: Optional[int] = None) -> Contract:
    """JXP101: the number of ``scan`` eqns anywhere in the program
    (sub-jaxprs included) matches. Pin ``expected`` exactly, or bound
    with ``min_count``/``max_count``."""
    label = f"scan_count(expected={expected}, min={min_count}, " \
            f"max={max_count})"

    def check(walk: Walk) -> List[ContractFinding]:
        n = len(walk.scans())
        problems = []
        if expected is not None and n != expected:
            problems.append(f"program has {n} scan(s), expected {expected}")
        if min_count is not None and n < min_count:
            problems.append(f"program has {n} scan(s), expected >= "
                            f"{min_count}")
        if max_count is not None and n > max_count:
            problems.append(f"program has {n} scan(s), expected <= "
                            f"{max_count}")
        return [ContractFinding("JXP101", label, "", m) for m in problems]

    return Contract("JXP101", "scan-count", label, check)


def scan_length(length: int, *, min_count: int = 1,
                forbid: bool = False) -> Contract:
    """JXP102: a ``scan`` of exactly ``length`` static ticks exists (at
    least ``min_count`` of them) — the zb dW-deferral witness ("a third
    scan of exactly M·v ticks"). ``forbid=True`` inverts it: NO scan of
    that length may exist (the 1f1b control: its dW rides the full
    backward sweep, so an M·v-length scan would mean the wrong schedule
    traced)."""
    label = f"scan_length({length}, min_count={min_count}, forbid={forbid})"

    def check(walk: Walk) -> List[ContractFinding]:
        hits = [s for s in walk.scans()
                if s.eqn.params.get("length") == length]
        if forbid:
            return [ContractFinding(
                "JXP102", label, s.path,
                f"forbidden scan of length {length} present")
                for s in hits]
        if len(hits) < min_count:
            got = sorted(s.eqn.params.get("length") for s in walk.scans()
                         if isinstance(s.eqn.params.get("length"), int))
            return [ContractFinding(
                "JXP102", label, "",
                f"expected >= {min_count} scan(s) of length {length}, "
                f"found {len(hits)} (lengths present: {got})")]
        return []

    return Contract("JXP102", "scan-length", label, check)


# --- JXP2xx: donation ---------------------------------------------------------

def donation_honored() -> Contract:
    """JXP201: no value read after its buffer was donated — a var passed
    in a donated position of a jit eqn must not feed any LATER eqn of
    the same level, nor that level's outputs (XLA may have reused the
    buffer; the read is use-after-free). Literals are skipped — a
    literal has no buffer to donate."""
    label = "donation_honored()"

    def check(walk: Walk) -> List[ContractFinding]:
        findings = []
        for path, jaxpr in walk.levels():
            seen_donation = set()
            for eqn in jaxpr.eqns:
                if seen_donation:
                    for var in eqn.invars:
                        if not hasattr(var, "val") and var in seen_donation:
                            findings.append(ContractFinding(
                                "JXP201", label, path,
                                f"donated buffer {var} is read by a later "
                                f"`{eqn.primitive.name}` eqn after the "
                                "jit call that donated it"))
                if _is_jit_eqn(eqn):
                    donated = eqn.params.get("donated_invars") or ()
                    for var, is_donated in zip(eqn.invars, donated):
                        if is_donated and not hasattr(var, "val"):
                            seen_donation.add(var)
            for var in getattr(jaxpr, "outvars", ()):
                if not hasattr(var, "val") and var in seen_donation:
                    findings.append(ContractFinding(
                        "JXP201", label, path,
                        f"donated buffer {var} is returned from the "
                        "enclosing program after donation"))
        return findings

    return Contract("JXP201", "donation-use-after-donate", label, check)


def donation_rebound() -> Contract:
    """JXP202: every donated operand has a same-aval output to rebind —
    a jit eqn donating an aval it produces fewer outputs of cannot
    reuse the buffer (jax warns 'Some donated buffers were not usable'
    at run time; this is the same check at trace time, multiset-matched
    per (shape, dtype))."""
    label = "donation_rebound()"

    def _aval_key(var):
        aval = getattr(var, "aval", None)
        return (tuple(getattr(aval, "shape", ())),
                str(getattr(aval, "dtype", "?")))

    def check(walk: Walk) -> List[ContractFinding]:
        findings = []
        for path, jaxpr in walk.levels():
            for eqn in jaxpr.eqns:
                if not _is_jit_eqn(eqn):
                    continue
                donated = eqn.params.get("donated_invars") or ()
                if not any(donated):
                    continue
                out_counts: dict = {}
                for var in eqn.outvars:
                    k = _aval_key(var)
                    out_counts[k] = out_counts.get(k, 0) + 1
                for var, is_donated in zip(eqn.invars, donated):
                    if not is_donated or hasattr(var, "val"):
                        continue
                    k = _aval_key(var)
                    if out_counts.get(k, 0) > 0:
                        out_counts[k] -= 1
                    else:
                        shape, dtype = k
                        findings.append(ContractFinding(
                            "JXP202", label, path,
                            f"donated operand {dtype}{list(shape)} has no "
                            "matching-aval output to rebind — the "
                            "donation buys nothing (jax: 'donated "
                            "buffers were not usable')"))
        return findings

    return Contract("JXP202", "donated-not-rebound", label, check)


# --- JXP3xx: aval shape -------------------------------------------------------

def no_aval_matching(pred: Callable[[Tuple[int, ...]], bool],
                     label: str) -> Contract:
    """JXP301: no eqn operand or output ANYWHERE in the program (Pallas
    kernel bodies excepted — their avals are VMEM tiles, while the claim
    is about HBM arrays; a kernel's HBM operands are still checked at
    its ``pallas_call`` eqn) has a shape matching ``pred``. The
    bucketed-bias memory witness:
    ``no_aval_matching(lambda s: sum(d >= seq for d in s) >= 2,
    "materialized O(s^2) bias/score")``."""
    full = f"no_aval_matching({label})"

    def check(walk: Walk) -> List[ContractFinding]:
        findings = []
        for site in walk.sites:
            if site.under_kernel():
                continue
            for var in list(site.eqn.invars) + list(site.eqn.outvars):
                shape = tuple(getattr(getattr(var, "aval", None), "shape",
                                      ()) or ())
                if shape and pred(shape):
                    findings.append(ContractFinding(
                        "JXP301", full, site.path,
                        f"aval {list(shape)} at `{site.prim}` matches "
                        f"forbidden pattern: {label}"))
        return findings

    return Contract("JXP301", "no-aval-matching", full, check)


# --- JXP4xx: collective inventory ---------------------------------------------

def _on_axis(eqn, axis: str) -> bool:
    return axis in collective_axes(eqn)


def no_full_width_all_gather(axis: str) -> Contract:
    """JXP401: no ``all_gather`` over ``axis`` anywhere in the program —
    the overlapped-ring acceptance (an explicit full-width gather of the
    activation is exactly what the ppermute ring exists to avoid; on an
    ``overlap_comm`` path its presence means the blocking fallback
    traced)."""
    label = f"no_full_width_all_gather({axis!r})"

    def check(walk: Walk) -> List[ContractFinding]:
        return [ContractFinding(
            "JXP401", label, s.path,
            f"full-width `{s.prim}` over axis {axis!r} "
            f"(payload {eqn_shapes(s.eqn)})")
            for s in walk.sites
            if s.prim in ("all_gather", "all_gather_invariant")
            and _on_axis(s.eqn, axis)]

    return Contract("JXP401", "no-full-width-all-gather", label, check)


def ppermute_present(axis: str) -> Contract:
    """JXP402: at least one ``ppermute`` over ``axis`` — the ring /
    pipeline-hop witness (its absence on an overlapped path means the
    ring never traced)."""
    label = f"ppermute_present({axis!r})"

    def check(walk: Walk) -> List[ContractFinding]:
        if any(s.prim == "ppermute" and _on_axis(s.eqn, axis)
               for s in walk.sites):
            return []
        return [ContractFinding(
            "JXP402", label, "",
            f"no ppermute over axis {axis!r} anywhere in the program")]

    return Contract("JXP402", "ppermute-present", label, check)


def collective_free_region(path_pattern: str, *,
                           region: str = "") -> Contract:
    """JXP403: no collective primitive in any eqn whose path matches
    ``path_pattern`` (a regex over the walker's ``/``-joined segments —
    scans embed their length, so the zb dW sweep is targetable as
    ``r"scan:12"``). A pattern matching NO site at all is itself a
    violation: a typo'd region must not silently pass. ``region`` names
    the region in messages."""
    name = region or path_pattern or "<whole program>"
    label = f"collective_free_region({name})"
    rx = re.compile(path_pattern)

    def check(walk: Walk) -> List[ContractFinding]:
        in_region = [s for s in walk.sites if rx.search(s.path)]
        if not in_region:
            return [ContractFinding(
                "JXP403", label, "",
                f"no eqn matches region pattern {path_pattern!r} — the "
                "region does not exist in this program")]
        return [ContractFinding(
            "JXP403", label, s.path,
            f"collective `{s.prim}` (axis {collective_axes(s.eqn)}) "
            f"inside the {name} region, declared collective-free")
            for s in in_region if collective_kind(s.eqn) is not None]

    return Contract("JXP403", "collective-free-region", label, check)


# --- JXP6xx: static peak memory (apexmem) -------------------------------------

def peak_memory_bound(limit_bytes: int, *,
                      arg_families: Optional[Sequence[str]] = None
                      ) -> Contract:
    """JXP601: the donation-aware static liveness peak
    (:func:`apex_tpu.lint.liveness.analyze`) of the whole traced
    program stays ``<= limit_bytes``. This is the per-entrypoint HBM
    gate ``python -m apex_tpu.lint --jaxpr --memory --budget-file F``
    enforces, usable directly in tests via :func:`assert_contracts`.
    ``arg_families`` optionally labels the program's flattened invars
    so the violation message carries the family breakdown."""
    label = f"peak_memory_bound({limit_bytes})"

    def check(walk: Walk) -> List[ContractFinding]:
        from apex_tpu.lint import liveness

        rep = liveness.analyze(walk.jaxpr, arg_families=arg_families)
        if rep.peak_bytes <= limit_bytes:
            return []
        fams = ", ".join(f"{k}={v}" for k, v in rep.families.items()
                         if v)
        return [ContractFinding(
            "JXP601", label, "",
            f"static peak HBM {rep.peak_bytes} bytes "
            f"({rep.peak_bytes / 2**20:.2f} MB) exceeds the bound "
            f"{limit_bytes} bytes ({limit_bytes / 2**20:.2f} MB); "
            f"at-peak families: {fams or 'none'}")]

    return Contract("JXP601", "peak-memory-bound", label, check)


def donation_aliased(name: str = "donated buffer", *,
                     min_bytes: int = 1) -> Contract:
    """JXP602: the liveness analysis finds at least ``min_bytes`` of
    donation-aliased buffer — i.e. some donated operand's bytes are
    provably counted ONCE (input aliased to a same-aval output), the
    serving invariant behind the donated-and-rebound paged pool.
    Stronger than JXP202 (which only checks a matching output *exists*):
    this asserts the alias survives the full liveness accounting —
    the donated buffer is dead at the donation point, so the rebind
    really reuses it. ``name`` labels the buffer in messages."""
    label = f"donation_aliased({name!r}, min_bytes={min_bytes})"

    def check(walk: Walk) -> List[ContractFinding]:
        from apex_tpu.lint import liveness

        rep = liveness.analyze(walk.jaxpr)
        if rep.donation_aliased_bytes >= min_bytes:
            return []
        return [ContractFinding(
            "JXP602", label, "",
            f"{name}: expected >= {min_bytes} donation-aliased bytes, "
            f"liveness found {rep.donation_aliased_bytes} — no donated "
            "operand is rebound in place (the pool would cost its "
            "bytes twice)")]

    return Contract("JXP602", "donation-aliased", label, check)


# --- JXP5xx: precision --------------------------------------------------------

def fp32_accumulation() -> Contract:
    """JXP501: no scan carry accumulated by ``add`` in bf16/fp16 — a
    low-precision running sum loses mantissa every tick (the reason the
    schedules' main grads and the ring dW folds accumulate in fp32 and
    downcast once at the end). A bf16 carry that is merely threaded
    (not add-produced) is fine."""
    label = "fp32_accumulation()"

    def check(walk: Walk) -> List[ContractFinding]:
        findings = []
        for site in walk.scans():
            num_carry = site.eqn.params.get("num_carry")
            body = None
            for val in site.eqn.params.values():
                for j in sub_jaxprs(val):
                    body = j
                    break
                if body is not None:
                    break
            if body is None or not isinstance(num_carry, int):
                continue
            producers = {}
            for eqn in body.eqns:
                for var in eqn.outvars:
                    producers[var] = eqn
            for var in list(body.outvars)[:num_carry]:
                prod = producers.get(var)
                if prod is None or prod.primitive.name not in _ACCUM_PRIMS:
                    continue
                dtype = str(getattr(getattr(var, "aval", None), "dtype", ""))
                if dtype in _LOW_PRECISION:
                    findings.append(ContractFinding(
                        "JXP501", label, site.path,
                        f"scan carry accumulated by `"
                        f"{prod.primitive.name}` in {dtype} — accumulate "
                        "in fp32 and downcast once after the scan"))
        return findings

    return Contract("JXP501", "fp32-accumulation", label, check)


def eqn_shapes(eqn) -> List[list]:
    """Operand shapes of one eqn (for messages)."""
    return [list(getattr(getattr(v, "aval", None), "shape", ()) or ())
            for v in eqn.invars]
