"""apexmem: donation-aware buffer-lifetime analysis over traced jaxprs.

The planner prices *time* from the exact traced bytes/FLOPs of
:func:`apex_tpu.lint.jaxpr_check.static_cost`; this module gives *memory*
the same treatment — a static peak-HBM bound read off the program the
compiler actually sees, instead of the hand closed form in
``apex_tpu/plan/cost.py`` that knows nothing about donation, zb dW
stashes, or the paged KV pool. AMP (arXiv:2210.07297) treats memory
feasibility as a first-class pruning predicate in strategy search;
apexmem is that predicate, derived from the trace (the veScale,
arXiv:2509.07003, argument: check the program, don't assume the math).

Liveness model (the contract the hand-computed fixtures in
``tests/test_liveness.py`` pin byte-exactly)
--------------------------------------------
Eqns are walked in execution order per sub-jaxpr level with a live-set
in bytes:

* a var is live from its defining eqn until after its **last use at
  that level** (level outputs live through the end);
* **pinned inputs** (the level's non-donated invars and constvars)
  stay resident for the whole level even if read early — the caller
  still owns those buffers;
* at each eqn the footprint is ``live-before + new output bytes +
  inner extra`` (outputs materialize while operands are still held);
* **donation aliases input to output**: at a ``jit`` eqn with
  ``donated_invars``, each donated operand at its last use is multiset-
  matched to a same-``(shape, dtype)`` output; the matched output takes
  over the donor's buffer (zero new bytes, family inherited) — a
  donated-and-rebound pool costs its bytes ONCE. The same reuse applies
  to a first-order eqn whose dying *transient* operand matches an
  output aval (XLA's buffer reuse of a freed operand) — but never
  across other higher-order eqns, whose operands coexist with their
  outputs for the body's whole duration;
* **scan** contributes ``carry + max-per-iteration-live + length×stash``:
  the stacked ys outputs ARE the ``length×stash`` term (their avals
  carry the leading length dim — zb's M·v deferred-dW stash is priced
  explicitly, tallied in ``stash_bytes`` and attributed to the
  ``activations`` family); a transient init-carry dying at the scan
  aliases the carry output (the working carry is double-buffer-free),
  and the body's per-iteration transient peak beyond its own inputs is
  the ``inner extra``;
* **cond** branches are alternatives: inner extra is the family-wise
  max over branches (the PR-10 branch-max idiom), never the sum;
* **while** trip counts are not static: the body contributes ONE
  iteration's extra and the site is tallied in
  ``unbounded_stash_sites`` — flagged, never silently multiplied;
* **Pallas kernel bodies are skipped** (VMEM tiles, not HBM); the
  ``pallas_call`` eqn's HBM operands/outputs are counted like any
  other eqn's;
* other sub-jaxpr eqns (jit/remat/shard_map/custom_vjp) descend with
  operand families and donation flags propagated; their contribution is
  the inner peak beyond the operand bytes already counted at this
  level (clamped family-wise at zero).

Every byte at the peak belongs to one **family** —
``params`` / ``optimizer`` / ``activations`` (batch inputs and scan
stashes) / ``kv_pool`` / ``temps`` (everything transient). Top-level
invars are labelled by the caller (``arg_families``, one label per
flattened invar — :func:`apex_tpu.lint.entrypoints.arg_families` builds
it for registered entrypoints); intermediates default to ``temps``
except scan stashes (``activations``) and donation-aliased outputs
(donor's family).

Like the rest of the lint package this module imports nothing outside
the stdlib: jaxprs are walked duck-typed, the analysis never imports
the jax it is vetting.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

from apex_tpu.lint.jaxpr_check import (
    _KERNEL_PRIMS,
    _is_jit_eqn,
    as_jaxpr,
    aval_bytes,
    sub_jaxprs,
)

#: the five HBM families every live byte is attributed to
FAMILIES = ("params", "optimizer", "activations", "kv_pool", "temps")


def _is_lit(var) -> bool:
    """Literals carry ``.val`` and have no buffer."""
    return hasattr(var, "val")


def _akey(var) -> Tuple[Tuple[int, ...], str]:
    aval = getattr(var, "aval", None)
    return (tuple(getattr(aval, "shape", ()) or ()),
            str(getattr(aval, "dtype", "?")))


@dataclasses.dataclass
class _Stats:
    peak: int
    peak_fams: Dict[str, int]
    aliased: int      #: bytes saved by jit donation aliasing
    stash: int        #: stacked scan-ys bytes (the length×stash term)
    whiles: int       #: while bodies seen (bound excludes trip count)
    eqns: int


@dataclasses.dataclass(frozen=True)
class MemoryReport:
    """The static peak-HBM bound of one traced program."""
    entrypoint: str
    peak_bytes: int
    families: Dict[str, int]          #: bytes per family AT the peak
    donation_aliased_bytes: int
    stash_bytes: int
    unbounded_stash_sites: int
    eqns: int

    def record(self) -> Dict[str, Any]:
        """The closed ``kind: "static_memory"`` artifact
        (:data:`apex_tpu.monitor.schema.STATIC_MEMORY_SCHEMA`, gated by
        ``tools/validate_metrics.py --static-memory``)."""
        from apex_tpu.monitor.registry import SCHEMA_VERSION

        return {
            "schema": SCHEMA_VERSION,
            "kind": "static_memory",
            "entrypoint": self.entrypoint,
            "peak_bytes": int(self.peak_bytes),
            "peak_mb": round(self.peak_bytes / 2 ** 20, 3),
            "families": {f: int(self.families.get(f, 0))
                         for f in FAMILIES},
            "donation_aliased_bytes": int(self.donation_aliased_bytes),
            "stash_bytes": int(self.stash_bytes),
            "unbounded_stash_sites": int(self.unbounded_stash_sites),
            "eqns": int(self.eqns),
            "source": "liveness",
        }


def _map_operands(name: str, eqn, sub, fam_of: Dict[Any, str]
                  ) -> Tuple[List[str], List[bool]]:
    """(families, reusable) for one sub-jaxpr's invars, propagated from
    the eqn operands they bind: jit carries its donation flags down
    (a donated inner input may die at its last inner use), a scan's
    carry slots are working buffers, everything else is pinned for the
    sub-level's duration. A layout we cannot map positionally (while's
    split cond/body consts) degrades to all-temps/pinned — an upper
    bound, never an undercount."""
    ops = list(eqn.invars)
    if name == "cond":
        ops = ops[1:]  # operand 0 is the branch index/predicate
    n = len(sub.invars)
    if len(ops) != n:
        return ["temps"] * n, [False] * n
    fams = ["temps" if _is_lit(v) else fam_of.get(v, "temps")
            for v in ops]
    reuse = [False] * n
    if _is_jit_eqn(eqn):
        donated = eqn.params.get("donated_invars") or ()
        if len(donated) == n:
            reuse = [bool(d) for d in donated]
    elif name == "scan":
        nc = eqn.params.get("num_consts")
        nk = eqn.params.get("num_carry")
        if isinstance(nc, int) and isinstance(nk, int) and nc + nk <= n:
            reuse = [False] * nc + [True] * nk + [False] * (n - nc - nk)
    return fams, reuse


def _level(j, fams: Sequence[str], reusable: Sequence[bool]) -> _Stats:
    eqns = list(j.eqns)
    n = len(eqns)

    # prepass: last use per var at THIS level, and donation points
    last_use: Dict[Any, int] = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if not _is_lit(v):
                last_use[v] = i
    for v in getattr(j, "outvars", ()):
        if not _is_lit(v):
            last_use[v] = n
    donated_at: Dict[Any, int] = {}
    for i, eqn in enumerate(eqns):
        if _is_jit_eqn(eqn):
            donated = eqn.params.get("donated_invars") or ()
            for v, d in zip(eqn.invars, donated):
                if d and not _is_lit(v) and v not in donated_at:
                    donated_at[v] = i

    pinned = set()
    fam_of: Dict[Any, str] = {}
    live: Dict[Any, int] = {}
    for v in getattr(j, "constvars", ()):
        fam_of[v] = "temps"
        pinned.add(v)
        live[v] = aval_bytes(v)
    for v, f, r in zip(j.invars, fams, reusable):
        fam_of[v] = f
        if not r:
            pinned.add(v)
        live[v] = aval_bytes(v)

    def _release(v) -> int:
        # donation consumes the buffer at the donating eqn (JXP201
        # guarantees no later read); pinned inputs live to level end
        if v in donated_at:
            return donated_at[v]
        if v in pinned:
            return n
        return last_use.get(v, -1)

    live_f = {f: 0 for f in FAMILIES}
    for v, b in live.items():
        live_f[fam_of[v]] += b
    peak = sum(live.values())
    peak_f = dict(live_f)
    aliased = stash = whiles = 0
    eqn_count = 0

    # inputs never read (and not donated/pinned) free right after entry
    for v in list(live):
        if _release(v) < 0:
            live_f[fam_of[v]] -= live.pop(v)

    for i, eqn in enumerate(eqns):
        eqn_count += 1
        name = eqn.primitive.name
        subs: List[Any] = []
        for val in eqn.params.values():
            subs.extend(sub_jaxprs(val))

        extra_f = {f: 0 for f in FAMILIES}
        if subs and name not in _KERNEL_PRIMS:
            per_sub = []
            for sub in subs:
                sfams, sreuse = _map_operands(name, eqn, sub, fam_of)
                st = _level(sub, sfams, sreuse)
                aliased += st.aliased
                stash += st.stash
                whiles += st.whiles
                eqn_count += st.eqns
                inv_f = {f: 0 for f in FAMILIES}
                for v, f in zip(sub.invars, sfams):
                    inv_f[f] += aval_bytes(v)
                per_sub.append({f: max(0, st.peak_fams[f] - inv_f[f])
                                for f in FAMILIES})
            for f in FAMILIES:
                extra_f[f] = max(ps[f] for ps in per_sub)
        if name == "while":
            whiles += 1

        # aliasing: which outputs take over a dying operand's buffer
        # instead of allocating. Three sound cases: (1) jit donation —
        # the caller handed the buffer over (tallied for JXP602);
        # (2) a scan's init carry dying at the scan — the running carry
        # slot reuses it (the carry is sequential, never coexistent);
        # (3) first-order eqns whose dying transient operand matches an
        # output aval — XLA's buffer reuse of a freed operand. Higher-
        # order eqns other than (1)/(2) get NO generic reuse: their
        # operands are read throughout the body while outputs are
        # written, so the buffers genuinely coexist.
        alias_fam: Dict[Any, str] = {}
        nk = eqn.params.get("num_carry") if name == "scan" else None
        avail_don: Dict[Any, List[Any]] = {}
        avail_gen: Dict[Any, List[Any]] = {}
        if _is_jit_eqn(eqn):
            donated = eqn.params.get("donated_invars") or ()
            for v, d in zip(eqn.invars, donated):
                if d and not _is_lit(v) and _release(v) == i:
                    avail_don.setdefault(_akey(v), []).append(v)
        elif name == "scan":
            nc = eqn.params.get("num_consts")
            if isinstance(nc, int) and isinstance(nk, int):
                for c in range(nk):
                    if nc + c >= len(eqn.invars) or c >= len(eqn.outvars):
                        break
                    v, o = eqn.invars[nc + c], eqn.outvars[c]
                    if (not _is_lit(v) and v not in pinned
                            and v not in donated_at
                            and _release(v) == i and _akey(v) == _akey(o)):
                        alias_fam[o] = fam_of[v]
        elif not subs:
            seen = set()
            for v in eqn.invars:
                if (not _is_lit(v) and v not in seen and v not in pinned
                        and v not in donated_at and _release(v) == i):
                    seen.add(v)
                    avail_gen.setdefault(_akey(v), []).append(v)
        for o in eqn.outvars:
            if o in alias_fam:
                continue
            k = _akey(o)
            if avail_don.get(k):
                alias_fam[o] = fam_of[avail_don[k].pop(0)]
                aliased += aval_bytes(o)
            elif avail_gen.get(k):
                alias_fam[o] = fam_of[avail_gen[k].pop(0)]

        out_fam: Dict[Any, str] = {}
        out_new_f = {f: 0 for f in FAMILIES}
        for idx, o in enumerate(eqn.outvars):
            if o in alias_fam:
                out_fam[o] = alias_fam[o]
                continue  # takes over the donor's live bytes
            if name == "scan" and isinstance(nk, int) and idx >= nk:
                out_fam[o] = "activations"  # stacked per-tick stash
                stash += aval_bytes(o)
            else:
                out_fam[o] = "temps"
            out_new_f[out_fam[o]] += aval_bytes(o)

        # scan/while outputs (stacked ys, the threaded carry) accumulate
        # WHILE the body runs, so they add to the body's transient peak;
        # a call-like eqn's outputs either already exist at the inner
        # peak moment (then they are inside `extra`) or do not exist yet
        # (then `out_new` is the larger later moment) — take the max,
        # not the sum, or every jit output double-counts.
        if subs and name not in _KERNEL_PRIMS and name not in (
                "scan", "while"):
            if sum(extra_f.values()) >= sum(out_new_f.values()):
                during_f = {f: live_f[f] + extra_f[f] for f in FAMILIES}
            else:
                during_f = {f: live_f[f] + out_new_f[f] for f in FAMILIES}
        else:
            during_f = {f: live_f[f] + out_new_f[f] + extra_f[f]
                        for f in FAMILIES}
        during = sum(during_f.values())
        if during > peak:
            peak, peak_f = during, during_f

        for v in [v for v in live if _release(v) == i]:
            live_f[fam_of[v]] -= live.pop(v)
        for o in eqn.outvars:
            if _is_lit(o):
                continue
            fam_of[o] = out_fam[o]
            if _release(o) > i:
                b = aval_bytes(o)
                live[o] = b
                live_f[fam_of[o]] += b

    return _Stats(peak, peak_f, aliased, stash, whiles, eqn_count)


def analyze(jaxpr_like, *, arg_families: Optional[Sequence[str]] = None,
            entrypoint: str = "") -> MemoryReport:
    """The static peak-HBM bound of one traced program.

    ``arg_families`` labels the program's (flattened) invars, one of
    :data:`FAMILIES` each — the length must match ``len(jaxpr.invars)``
    exactly (a silently mislabelled operand would corrupt the family
    breakdown). ``None`` labels every input ``temps``: the peak is
    still exact, only the attribution is flat.
    """
    j = as_jaxpr(jaxpr_like)
    invars = list(j.invars)
    if arg_families is None:
        fams: List[str] = ["temps"] * len(invars)
    else:
        fams = list(arg_families)
        if len(fams) != len(invars):
            raise ValueError(
                f"arg_families has {len(fams)} labels for "
                f"{len(invars)} jaxpr invars — pass one label per "
                "flattened input leaf")
        bad = sorted(set(fams) - set(FAMILIES))
        if bad:
            raise ValueError(
                f"unknown families {bad}; valid: {list(FAMILIES)}")
    st = _level(j, fams, [False] * len(invars))
    return MemoryReport(
        entrypoint=entrypoint,
        peak_bytes=st.peak,
        families={f: st.peak_fams.get(f, 0) for f in FAMILIES},
        donation_aliased_bytes=st.aliased,
        stash_bytes=st.stash,
        unbounded_stash_sites=st.whiles,
        eqns=st.eqns,
    )
