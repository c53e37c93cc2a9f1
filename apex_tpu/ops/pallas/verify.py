"""Fused verify-and-sample Pallas kernel: k+1 target logit rows + k
drafted tokens → (longest accepted prefix, corrected next token), one
kernel.

Speculative decoding's verification tail is, composed in XLA, a chain of
O(k·V) staging ops — scale, filter, per-row softmax/argmax, a prefix
scan over the accept flags, a gather of the corrected row — each
materializing an O(V) tensor between HBM round trips, exactly the
per-token-epilogue traffic arXiv:2502.17728 argues into one kernel (and
exactly what :mod:`apex_tpu.ops.pallas.sampling` already fused for the
single-row sampling tail). This kernel extends that fusion to the whole
accept/reject tail: the (k+1, V) logit block is read into VMEM once and
two int32 lanes come back — nothing O(V) returns to HBM.

Acceptance semantics (the drafters propose point-mass — greedy — drafts,
so both modes are EXACT: the emitted stream is distributed identically
to non-speculative decoding):

* **Greedy** (temperature == 0): row i's candidate is ``argmax`` of the
  target's i-th logit row; drafted token i is accepted iff it equals
  candidate i. The accepted prefix length ``a`` is the count of leading
  matches, and the corrected next token is candidate ``a`` — by
  construction the token the non-speculative greedy loop would have
  produced, so the spec stream is token-identical to the baseline.
* **Rejection sampling** (temperature > 0, top-k/top-p): the target
  distribution p is the same temperature→top-k→top-p filtered softmax
  the fused sampling tail draws from (the bisection helpers of
  :mod:`~apex_tpu.ops.pallas.sampling` are reused verbatim). A drafted
  token d_i — a point mass under the drafter — is accepted with
  probability p(d_i) (the ``min(1, p/q)`` rule with q = δ(d_i)); on the
  first rejection the corrected token is drawn from the residual
  ``p`` with d_i removed (the normalized ``max(p − q·min(p,q), 0)`` of
  a point-mass q), and if all k drafts are accepted the bonus token is
  drawn from the full filtered p. Both draws are Gumbel-argmax on
  pre-drawn uniform rows, shared with the XLA fallback.

The filtering/acceptance math lives in module-level helpers written for
arbitrary leading batch dims, shared VERBATIM with the XLA fallback in
:mod:`apex_tpu.ops.fused_verify` — kernel/fallback parity is by
construction on shared noise, the same discipline as ``fused_sample``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.pallas.attention import _LSE_LANES
from apex_tpu.ops.pallas.sampling import (FILTERED, filtered_scaled,
                                          gumbel_argmax,
                                          whole_row_vmem_limit)

#: sentinel drafted id for the bonus row (row k has no draft to verify);
#: never equals a real candidate, so its accept flag is always False and
#: the accepted prefix length is capped at k
NO_DRAFT = -1

# Shape convention of every helper below (shared VERBATIM by the kernels
# and the XLA fallback): logits are (..., R, V) with one verify row per
# sublane; every per-row quantity is a COLUMN (..., R, 1) and every
# per-batch result a (..., 1, 1) cell. Mosaic has no layout for rank-1
# intermediates and cannot turn a lane-oriented vector into a
# sublane-oriented one (``x[:, None]``), so the math never leaves rank 2:
# reductions over the vocab keep a 1-wide lane dim, reductions over the
# rows keep a 1-high sublane dim, and the one place that needs a row
# vector from a column goes through :func:`_col_to_row`.


def _rows(x):
    """int32 row index of every element of a (..., R, W) array."""
    return jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 2)


def row_argmax(s):
    """Row-wise argmax with ties to the LOWEST index (``jnp.argmax``'s
    convention, so greedy spec candidates match the engines' greedy
    tails bit for bit). ``s`` (..., R, V) → (..., R, 1) int32."""
    m = jnp.max(s, axis=-1, keepdims=True)
    V = s.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1)
    return jnp.min(jnp.where(s == m, idx, V), axis=-1, keepdims=True)


def accepted_prefix_len(acc):
    """Length of the leading run of True accept flags: ``acc`` (..., k+1,
    1) bool → (..., 1, 1) int32 in [0, k] (the bonus row's flag is always
    False — :data:`NO_DRAFT` never matches a candidate). The run length
    IS the index of the first False row."""
    n = acc.shape[-2]
    return jnp.min(jnp.where(acc, n, _rows(acc)), axis=-2, keepdims=True)


def select_row(vals, a):
    """``vals[..., a, 0]`` at a traced per-batch row index: ``vals``
    (..., R, 1), ``a`` (..., 1, 1) → (..., 1, 1), without a gather —
    a one-hot sum over the row axis (VPU-only, kernel-safe)."""
    return jnp.sum(jnp.where(_rows(vals) == a, vals, 0), axis=-2,
                   keepdims=True)


def _col_to_row(col):
    """(..., R, 1) column → (..., 1, R) row holding the same values, by
    masking the lane-broadcast column to the diagonal and summing over
    rows — a transpose built from iota/compare/select/reduce only."""
    n = col.shape[-2]
    shape = col.shape[:-1] + (n,)
    diag = (jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 2)
            == jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1))
    return jnp.sum(jnp.where(diag, col, 0), axis=-2, keepdims=True)


def verify_greedy(logits, drafted):
    """Exact greedy acceptance. ``logits`` (..., k+1, V); ``drafted``
    (..., k+1, 1) int32 with the bonus row pinned at :data:`NO_DRAFT`.
    Returns ``(accept_len, next_token)``, each (..., 1, 1) int32."""
    cand = row_argmax(logits.astype(jnp.float32))
    a = accepted_prefix_len(cand == drafted)
    return a, select_row(cand, a)


def verify_sampled(logits, drafted, u_acc, u_gum, *, temperature,
                   top_k, top_p):
    """Exact rejection-sampling acceptance for point-mass drafts under
    the temperature→top-k→top-p filtered target distribution.

    ``logits`` (..., k+1, V); ``drafted`` (..., k+1, 1) int32 (bonus row
    :data:`NO_DRAFT`); ``u_acc`` (..., k+1, 1) uniform acceptance draws
    in (0, 1]; ``u_gum`` (..., k+1, V) uniform Gumbel noise in (0, 1].
    Row i accepts d_i iff ``u_acc_i < p(d_i)``; every row's correction
    candidate is drawn from p with its drafted token FILTERED (the exact
    point-mass residual; the bonus row draws from the full p), and the
    first rejected row's candidate is the emitted correction. A drafted
    token the top-k/top-p filter removed carries p == 0 and is always
    rejected — the filters bind identically to the non-speculative tail.
    Returns ``(accept_len, next_token)``, each (..., 1, 1) int32.
    """
    s = filtered_scaled(logits, temperature=temperature, top_k=top_k,
                        top_p=top_p)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1)
    onehot = cols == drafted
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    p_d = (jnp.sum(jnp.where(onehot, e, 0.0), axis=-1, keepdims=True)
           / jnp.sum(e, axis=-1, keepdims=True))
    a = accepted_prefix_len(u_acc < p_d)
    cand = gumbel_argmax(jnp.where(onehot, FILTERED, s), u_gum,
                         keepdims=True)
    return a, select_row(cand, a)


def tree_depths(anc):
    """Per-node depth from the ancestor-or-self closure: ``anc``
    (..., N1, N1) int32 (``anc[i, j] == 1`` iff node j lies on node i's
    root path, including i itself and the root, node 0) → (..., N1, 1)
    int32 depths (the root has depth 0)."""
    return jnp.sum(anc.astype(jnp.int32), axis=-1, keepdims=True) - 1


def tree_accepted_path(acc, anc):
    """The deepest fully-accepted root path of a draft tree.

    ``acc`` (..., N1, 1) per-node accept flags (node 0 — the committed
    pending token — is forced accepted here; padding nodes must arrive
    False); ``anc`` (..., N1, N1) the ancestor-or-self closure. A node
    is PATH-accepted iff every node on its root path is accepted, and
    the winner is the deepest path-accepted node (ties to the LOWEST
    node index — the drafters order siblings best-first, so the tie
    break is deterministic and drafter-meaningful). Returns
    ``(accept_len, j_star)``, each (..., 1, 1) int32: the winner's depth
    (== accepted drafted tokens) and its node index. Node 0 is always
    path-accepted, so ``accept_len >= 0`` and ``j_star`` is always a
    valid node."""
    n1 = anc.shape[-1]
    rows = _rows(acc)
    acc_i = jnp.maximum(acc.astype(jnp.int32), (rows == 0).astype(jnp.int32))
    bad = anc.astype(jnp.int32) * (1 - _col_to_row(acc_i))
    ok = jnp.sum(bad, axis=-1, keepdims=True) == 0
    depth = tree_depths(anc)
    a = jnp.max(jnp.where(ok, depth, -1), axis=-2, keepdims=True)
    hit = ok & (depth == a)
    j_star = jnp.min(jnp.where(hit, rows, n1), axis=-2, keepdims=True)
    return a, j_star


def _from_parent(parents, vals):
    """``out[..., c, :] = vals[..., parents[c], :]`` — each node reads its
    PARENT's row of ``vals`` (..., N1, W): a select-sum over the N1
    (static) candidate parent rows, kernel-safe (sublane-broadcast row
    slices, no dynamic gather). ``parents`` (..., N1, 1) int32."""
    out = jnp.zeros(vals.shape, vals.dtype)
    for r in range(vals.shape[-2]):
        out = out + jnp.where(parents == r, vals[..., r:r + 1, :], 0)
    return out


def verify_tree_greedy(logits, tokens, parents, anc):
    """Exact greedy tree acceptance. ``logits`` (..., N1, V): row j is
    the target's distribution AFTER node j's token (row 0 after the
    committed pending token); ``tokens`` (..., N1, 1) int32 node tokens
    with row 0 pinned at :data:`NO_DRAFT`; ``parents`` (..., N1, 1) int32
    parent pointers (``parents[0] == 0``, ``parents[j] < j`` —
    topological); ``anc`` (..., N1, N1) the ancestor-or-self closure.

    Node j is accepted iff its parent's argmax candidate equals
    ``tokens[j]`` — exactly the chain rule applied edge-wise, so at
    branching 1 this degenerates to :func:`verify_greedy` (with the
    chain's row i living at node i+1). The emitted path is the deepest
    fully-accepted one and the bonus/corrected token is the winner
    row's candidate; by the same maximality argument as the chain
    (a child carrying the winner's candidate would itself be accepted,
    contradicting maximality), the result is token-identical to
    non-speculative greedy decoding. Returns ``(accept_len, j_star,
    next_token)``, each (..., 1, 1) int32."""
    cand = row_argmax(logits.astype(jnp.float32))        # (..., N1, 1)
    acc = (_from_parent(parents, cand) == tokens) & (tokens != NO_DRAFT)
    a, j_star = tree_accepted_path(acc, anc)
    return a, j_star, select_row(cand, j_star)


def verify_tree_sampled(logits, tokens, parents, anc, u_acc, u_gum, *,
                        temperature, top_k, top_p):
    """Rejection-sampling tree acceptance for point-mass drafts under
    the temperature→top-k→top-p filtered target distribution.

    Same operand contract as :func:`verify_tree_greedy` plus ``u_acc``
    (..., N1, 1) uniform acceptance draws in (0, 1] (row 0 unused) and
    ``u_gum`` (..., N1, V) uniform Gumbel noise. Node j accepts iff
    ``u_acc[j] < p_parent(tokens[j])`` (the ``min(1, p/q)`` rule with
    a point-mass q, applied edge-wise along every root path); the
    correction candidate of each row is drawn from p with ALL of that
    node's drafted children FILTERED (the point-mass residual over the
    set of drafts rejected at that node — the chain's single-child
    filter, generalized), and the winner row's candidate is emitted.
    At branching 1 this degenerates to :func:`verify_sampled` edge for
    edge. A drafted token the filter removed carries p == 0 and is
    always rejected."""
    s = filtered_scaled(logits, temperature=temperature, top_k=top_k,
                        top_p=top_p)                     # (..., N1, V)
    n1 = s.shape[-2]
    real = tokens != NO_DRAFT
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    # row c of e_par is node c's PARENT's unnormalized distribution, so
    # the edge probability is one masked row reduction per node
    e_par = _from_parent(parents, e)
    p_edge = (jnp.sum(jnp.where(cols == tokens, e_par, 0.0), axis=-1,
                      keepdims=True)
              / jnp.sum(e_par, axis=-1, keepdims=True))
    acc = (u_acc < p_edge) & real
    a, j_star = tree_accepted_path(acc, anc)
    # child[r, v]: some real drafted child of node r carries token v —
    # the correction row r filters every child token it just rejected
    # Each child's (1, 1) token cell widens along the lanes only (against
    # one vocab row) and its parent test down the rows only; their AND
    # is the (N1, V) mask. Comparing the cell against the full (N1, V)
    # iota directly is refused by Mosaic: "Not implemented: Broadcast in
    # both sublanes and lanes".
    rows = _rows(tokens)
    vocab_row = cols[..., :1, :]
    child = jnp.zeros(s.shape, jnp.bool_)
    for c in range(1, n1):  # node 0 is nobody's drafted child; a
        # NO_DRAFT (not real) node matches no vocab column
        child = child | ((rows == parents[..., c:c + 1, :])
                         & (vocab_row == tokens[..., c:c + 1, :]))
    cand = gumbel_argmax(jnp.where(child, FILTERED, s), u_gum,
                         keepdims=True)
    return a, j_star, select_row(cand, j_star)


# --- kernels ------------------------------------------------------------------
#
# One grid step per batch row. Blocks are (1, R, ·) slices of rank-3
# operands, so their last two dims EQUAL the arrays' — per-row operands
# ride as (b, R, 1) columns and per-batch results as (b, 1, 8) carriers (a
# (1, 128) block over a (b, 128) array is refused by Mosaic: a
# second-minor block dim of 1 is neither a multiple of 8 nor the full dim).

def _col_spec(rows):
    return pl.BlockSpec((1, rows, 1), lambda i: (i, 0, 0))


def _out_carrier(b, n):
    spec = pl.BlockSpec((1, 1, _LSE_LANES), lambda i: (i, 0, 0))
    shape = jax.ShapeDtypeStruct((b, 1, _LSE_LANES), jnp.int32)
    return [spec] * n, [shape] * n


def _compiler_params(rows, vocab):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",),
        vmem_limit_bytes=whole_row_vmem_limit(rows, vocab))


def _verify_kernel(logits_ref, drafted_ref, *refs, temperature, top_k,
                   top_p, sampled):
    """One grid row: the whole (k+1, V) logit block is VMEM-resident;
    every reduction below runs on it in place — the only HBM traffic is
    the block reads and two 8-lane int32 writes."""
    if sampled:
        u_acc_ref, u_gum_ref, a_ref, tok_ref = refs
        a, tok = verify_sampled(logits_ref[0], drafted_ref[0], u_acc_ref[0],
                                u_gum_ref[0], temperature=temperature,
                                top_k=top_k, top_p=top_p)
    else:
        a_ref, tok_ref = refs
        a, tok = verify_greedy(logits_ref[0], drafted_ref[0])
    a_ref[0] = jnp.broadcast_to(a, (1, _LSE_LANES))
    tok_ref[0] = jnp.broadcast_to(tok, (1, _LSE_LANES))


def fused_verify_fwd(logits, drafted, u_acc, u_gum, *, temperature,
                     top_k, top_p, interpret=False):
    """(b, k+1, V) logits + (b, k+1, 1) drafts/noise columns →
    ``(accept_len (b,), next_token (b,))`` int32; one kernel invocation,
    grid over batch rows. Greedy mode (``temperature == 0``) takes
    ``u_acc``/``u_gum`` as None. V must be a 128-multiple (lane tiling);
    the op-level wrapper gates on that."""
    b, k1, V = logits.shape
    sampled = temperature > 0.0
    logits_spec = pl.BlockSpec((1, k1, V), lambda i: (i, 0, 0))
    in_specs = [logits_spec, _col_spec(k1)]
    args = [logits, drafted]
    if sampled:
        in_specs.extend([_col_spec(k1), logits_spec])
        args.extend([u_acc, u_gum])
    out_specs, out_shape = _out_carrier(b, 2)
    a, tok = pl.pallas_call(
        functools.partial(_verify_kernel, temperature=temperature,
                          top_k=top_k, top_p=top_p, sampled=sampled),
        name="fused_verify",
        grid=(b,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_compiler_params(k1, V),
        interpret=interpret,
    )(*args)
    return a[:, 0, 0], tok[:, 0, 0]


def _verify_tree_kernel(logits_ref, tokens_ref, parents_ref, anc_ref,
                        *refs, temperature, top_k, top_p, sampled):
    """One grid row of the TREE verify: the whole (N1, V) logit block is
    VMEM-resident; the parent-pointer walk, per-edge acceptance, path
    max, and correction draw all run on it in place — three 8-lane
    int32 writes come back."""
    if sampled:
        u_acc_ref, u_gum_ref, a_ref, j_ref, tok_ref = refs
        a, j_star, tok = verify_tree_sampled(
            logits_ref[0], tokens_ref[0], parents_ref[0], anc_ref[0],
            u_acc_ref[0], u_gum_ref[0], temperature=temperature,
            top_k=top_k, top_p=top_p)
    else:
        a_ref, j_ref, tok_ref = refs
        a, j_star, tok = verify_tree_greedy(
            logits_ref[0], tokens_ref[0], parents_ref[0], anc_ref[0])
    a_ref[0] = jnp.broadcast_to(a, (1, _LSE_LANES))
    j_ref[0] = jnp.broadcast_to(j_star, (1, _LSE_LANES))
    tok_ref[0] = jnp.broadcast_to(tok, (1, _LSE_LANES))


def fused_verify_tree_fwd(logits, tokens, parents, anc, u_acc, u_gum, *,
                          temperature, top_k, top_p, interpret=False):
    """(b, N1, V) logits + (b, N1, 1) tree operand columns + the
    (b, N1, N1) closure → ``(accept_len (b,), j_star (b,), next_token
    (b,))`` int32; one kernel invocation, grid over batch rows. Greedy
    mode takes ``u_acc``/``u_gum`` as None. V must be a 128-multiple."""
    b, n1, V = logits.shape
    sampled = temperature > 0.0
    logits_spec = pl.BlockSpec((1, n1, V), lambda i: (i, 0, 0))
    in_specs = [logits_spec, _col_spec(n1), _col_spec(n1),
                pl.BlockSpec((1, n1, n1), lambda i: (i, 0, 0))]
    args = [logits, tokens, parents, anc]
    if sampled:
        in_specs.extend([_col_spec(n1), logits_spec])
        args.extend([u_acc, u_gum])
    out_specs, out_shape = _out_carrier(b, 3)
    a, j_star, tok = pl.pallas_call(
        functools.partial(_verify_tree_kernel, temperature=temperature,
                          top_k=top_k, top_p=top_p, sampled=sampled),
        name="fused_verify_tree",
        grid=(b,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_compiler_params(n1, V),
        interpret=interpret,
    )(*args)
    return a[:, 0, 0], j_star[:, 0, 0], tok[:, 0, 0]
