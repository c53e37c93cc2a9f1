"""Fused sampling-tail Pallas kernel: logits → temperature/top-k/top-p →
sampled token, one kernel.

The decode tail the engines used to run is a chain of host-visible XLA
ops — scale, ``lax.top_k`` (sort!), filter, sort+cumsum for top-p,
categorical — each materializing an O(V) tensor between HBM round trips.
At decode rates that tail is pure staging traffic on a memory-bound path
("LLM Inference Acceleration via Efficient Operation Fusion",
arXiv:2502.17728 makes exactly this argument for fusing the per-token
epilogue). This kernel reads the logits row and a pre-drawn uniform row
ONCE into VMEM and emits a single int32 per row; no O(V) intermediate
ever returns to HBM.

Two ideas make full top-k *and* top-p fusible without an in-kernel sort:

* **Threshold by bisection, not by sorting.** The top-k filter only
  needs the k-th largest VALUE; ``count(s >= t) >= k`` is a monotone
  step function of ``t``, so ~48 VPU-cheap bisection steps over the
  whole-row VMEM resident pin the threshold to one float32 ulp — at
  which point the kept set {s >= t_lo} equals the sort-based
  {s >= kth} exactly (ties at the k-th value are all kept, the same
  convention as ``jnp.where(s < kth, ...)``). Top-p is the same
  bisection on the monotone unnormalized mass ``sum(exp(s - m) where
  s >= t)`` against ``p * Z``: the kept set is the minimal
  highest-probability set with mass >= p — the sorted-cumsum definition
  — without materializing a sort.
* **Gumbel-argmax instead of cumulative inverse-CDF.** With u ~ U(0,1),
  ``argmax(s + (-log(-log u)))`` IS a categorical draw over
  ``softmax(s)`` — one elementwise op + one reduction, no normalized
  probability vector, no scan.

The uniform row is drawn by ``jax.random`` in the caller's jit (interpret
mode has no TPU PRNG lowering, and a shared operand keeps the kernel and
the XLA fallback bit-comparable); it fuses into the same program, so the
"tail" stays one dispatch. The filtering math lives in module-level
helpers shared VERBATIM with the XLA fallback in
:mod:`apex_tpu.ops.sampling` — parity is by construction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.pallas.attention import (_LSE_LANES, _VMEM_CAP,
                                           _vmem_limit)

# masked-out logit value (matches apex_tpu.inference.sampling._FILTERED):
# finite so a pathologically over-filtered row degrades to near-uniform
# over the kept set instead of NaN
FILTERED = -1e30

# bisection steps: each halves the threshold interval; ~30 reach one ulp
# of float32 values at logit magnitudes, 48 leaves margin (still ~100x
# cheaper than a V-length sort and all VMEM-resident)
_BISECT_ITERS = 48


def _bisect(s, keep_mass, target, lo=None, iters=_BISECT_ITERS):
    """Largest threshold t (per row) with ``mass(s >= t) >= target``,
    where ``mass`` counts elements (top-k) or sums ``keep_mass`` weights
    (top-p). ``s`` (rows, V) fp32; returns (rows, 1). The answer is an
    order statistic of ``s``, so once the interval collapses below one
    ulp the *kept set* {s >= lo} is exact. ``lo`` overrides the lower
    bound — it must still satisfy ``mass(s >= lo) >= target``: callers
    on already-FILTERED rows pass the min over LIVE entries, because a
    [-1e30, max] interval cannot collapse to a ulp in any finite number
    of halvings (the filtered sentinel would turn the search into a
    no-op)."""
    if lo is None:
        lo = jnp.min(s, axis=-1, keepdims=True)
    hi = jnp.max(s, axis=-1, keepdims=True)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        mass = jnp.sum(jnp.where(s >= mid, keep_mass, 0.0), axis=-1,
                       keepdims=True)
        ok = mass >= target
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return lo


def apply_top_k(s, top_k):
    """Keep each row's ``top_k`` largest entries (ties at the k-th value
    all kept); rest → FILTERED. ``s`` (rows, V) fp32, ``top_k`` static."""
    ones = jnp.ones(s.shape, jnp.float32)
    t = _bisect(s, ones, jnp.float32(top_k))
    return jnp.where(s >= t, s, FILTERED)


def apply_top_p(s, top_p):
    """Nucleus filter: keep the minimal highest-probability set whose
    softmax mass reaches ``top_p`` (the sorted-cumsum definition,
    crossing token included); rest → FILTERED. ``s`` (rows, V) fp32
    (post top-k: FILTERED entries carry exp()==0 mass), ``top_p``
    static."""
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    z = jnp.sum(e, axis=-1, keepdims=True)
    # bisect over the LIVE value range: after a top-k pass the row min is
    # the FILTERED sentinel, and [-1e30, max] never collapses in 48
    # halvings — the threshold would land below every real logit and
    # keep the whole top-k set (top-p silently off). Filtered entries
    # carry ~0 mass, so mass(>= live-min) is still >= top_p * z.
    lo = jnp.min(jnp.where(s > FILTERED * 0.5, s, m), axis=-1,
                 keepdims=True)
    t = _bisect(s, e, jnp.float32(top_p) * z, lo=lo)
    return jnp.where(s >= t, s, FILTERED)


def gumbel_argmax(s, u, keepdims=False):
    """One categorical draw over softmax(s) per row via the Gumbel trick;
    ties broken to the lowest index (argmax convention). ``u`` uniform in
    (0, 1] — the caller clamps 0 away so log(u) is finite. The kernels
    pass ``keepdims=True``: Mosaic has no layout for the rank-1 result."""
    g = -jnp.log(-jnp.log(u))
    x = s + g
    m = jnp.max(x, axis=-1, keepdims=True)
    V = x.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.min(jnp.where(x == m, idx, V), axis=-1, keepdims=keepdims)


def filtered_scaled(logits, *, temperature, top_k, top_p):
    """The shared tail: fp32 cast → 1/T scale → top-k → top-p. Static
    knobs select the program (no runtime branches — the serving engines'
    zero-recompile contract)."""
    s = logits.astype(jnp.float32) * (1.0 / temperature)
    if top_k > 0:
        s = apply_top_k(s, top_k)
    if top_p < 1.0:
        s = apply_top_p(s, top_p)
    return s


#: logits rows per grid step: one full sublane tile, so every whole-row
#: reduction fills its vregs (a 1-row block would use 1 sublane in 8)
_ROWS = 8


def _whole_row_bytes(rows, vocab):
    """VMEM a kernel needs to keep ``rows`` whole fp32 vocab rows resident
    (sublane-padded to 8) and run the bisection filters over them: the
    double-buffered operand blocks plus the filter's live temporaries
    (scaled row, exp row, Gumbel row, index iota, per-step where/compare)
    come to under 24 row-blocks."""
    return 24 * (-(-rows // 8) * 8) * vocab * 4


def whole_rows_fit(rows, vocab) -> bool:
    """Whether that many whole rows fit the VMEM a kernel may ask for
    (100 of a v5e's 128 MiB) — part of the sampling and verify kernels'
    eligibility gates."""
    return _whole_row_bytes(rows, vocab) <= _VMEM_CAP


def whole_row_vmem_limit(rows, vocab):
    """``vmem_limit_bytes`` for such a kernel. Mosaic's default scoped
    limit on a v5e is 16 MiB, which 8 rows of a 32768 vocab (1 MiB a
    block) already crowd and a larger vocab exceeds."""
    return _vmem_limit(_whole_row_bytes(rows, vocab))


def _sample_kernel(logits_ref, u_ref, o_ref, *, temperature, top_k, top_p):
    """One grid step: ``_ROWS`` whole logits rows are VMEM-resident, every
    reduction below runs on them in place — the only HBM traffic is the
    two block reads and the 8-lane index write."""
    s = filtered_scaled(logits_ref[:], temperature=temperature,
                        top_k=top_k, top_p=top_p)
    idx = gumbel_argmax(s, u_ref[:], keepdims=True)
    o_ref[:] = jnp.broadcast_to(idx, o_ref.shape)


def fused_sample_fwd(logits, u, *, temperature, top_k, top_p,
                     interpret=False):
    """(b, V) logits + (b, V) uniform noise → (b,) int32 tokens; one
    kernel invocation, grid over ``_ROWS``-row blocks (the batch is padded
    up to a whole number of them — a (1, V) block over (b, V) is refused
    by Mosaic: a second-minor block dim must be a multiple of 8 or the
    full dim). V must be a 128-multiple (lane tiling); the op-level
    wrapper gates on that."""
    b, V = logits.shape
    pad = -b % _ROWS
    if pad:  # pad rows sample garbage that is sliced away below
        logits = jnp.pad(logits, ((0, pad), (0, 0)))
        u = jnp.pad(u, ((0, pad), (0, 0)), constant_values=1.0)
    out = pl.pallas_call(
        functools.partial(_sample_kernel, temperature=temperature,
                          top_k=top_k, top_p=top_p),
        name="fused_sample",
        grid=((b + pad) // _ROWS,),
        in_specs=[
            pl.BlockSpec((_ROWS, V), lambda i: (i, 0)),
            pl.BlockSpec((_ROWS, V), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_ROWS, _LSE_LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b + pad, _LSE_LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=whole_row_vmem_limit(_ROWS, V)),
        interpret=interpret,
    )(logits, u)
    return out[:b, 0]
