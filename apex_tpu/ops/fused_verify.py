"""Fused speculative-decoding verification: k+1 logit rows + k drafted
ids → (longest accepted prefix, corrected next token) in ONE tail.

The op-level wrapper over :mod:`apex_tpu.ops.pallas.verify` following
the house dispatch rule (:mod:`apex_tpu.ops._backend`): the Pallas
kernel on TPU when the vocab tiles the lane dim, interpret-mode Pallas
under ``APEX_TPU_PALLAS=interpret``, and an XLA composition otherwise.
The XLA fallback calls the SAME module-level acceptance helpers the
kernel body runs, so the two paths agree token-for-token on shared
noise — the parity anchor ``tests/test_spec.py`` pins, the same
discipline as :func:`apex_tpu.ops.fused_sample`.

This is the speculative engines' verification tail (one fused dispatch
per spec round, :class:`apex_tpu.inference.DecodeEngine` and
:class:`apex_tpu.serving.ServingEngine`); the acceptance math is
documented in :mod:`apex_tpu.ops.pallas.verify`.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.ops import _backend
from apex_tpu.ops.pallas.sampling import whole_rows_fit
from apex_tpu.ops.pallas.verify import (NO_DRAFT, fused_verify_fwd,
                                        fused_verify_tree_fwd,
                                        verify_greedy, verify_sampled,
                                        verify_tree_greedy,
                                        verify_tree_sampled)


def verify_kernel_ok(rows: int, vocab: int, dtype) -> bool:
    """Mosaic eligibility: the vocab is the lane dim of every whole-row
    reduction (same rule as the fused sampling tail), f16 has no Mosaic
    support, and the ``rows`` whole vocab rows one grid step keeps
    resident must fit the VMEM budget the kernel asks for (at vocab
    32768 that admits k + 1 <= 32 rows; the 33 rows of the longest
    draft the drafters allow take the XLA composition)."""
    return (vocab % 128 == 0 and dtype != jnp.float16
            and whole_rows_fit(rows, vocab))


def _col(x):
    """(b, R) per-row operand → the (b, R, 1) column form the shared
    verify math and the kernels take (see ``ops.pallas.verify``)."""
    return None if x is None else x[..., None]


def _cells(*xs):
    """The (b, 1, 1) result cells of the XLA fallback → (b,) each."""
    return tuple(x[:, 0, 0] for x in xs)


def fused_verify(logits: jax.Array, drafted: jax.Array,
                 key: Optional[jax.Array] = None, *,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, impl: str = "auto"
                 ) -> Tuple[jax.Array, jax.Array]:
    """Verify ``k`` drafted tokens against ``k+1`` target logit rows.

    ``logits`` (b, k+1, V): row i is the target's distribution for the
    token AFTER the prefix plus i accepted drafts (row k is the bonus
    position when every draft is accepted). ``drafted`` (b, k) int32.
    Returns ``(accept_len (b,), next_token (b,))`` int32: the longest
    accepted draft prefix per row, and the corrected token sampled from
    row ``accept_len`` — so one spec round emits
    ``drafted[:accept_len] + [next_token]``, between 1 and k+1 tokens.

    ``temperature == 0`` is exact greedy acceptance (the spec stream is
    token-identical to non-speculative greedy decoding — the parity the
    engines witness). ``temperature > 0`` is exact rejection-sampling
    acceptance for point-mass (greedy) drafts under the same
    temperature→top-k→top-p filtered distribution the fused sampling
    tail draws from. All knobs are STATIC — they select the compiled
    program, never retrace per round.

    The uniform noise is drawn inside the caller's jit by ``jax.random``
    and consumed by the kernel in the same program; kernel and XLA
    fallback share it, so ``impl`` never changes the verdict.
    """
    if logits.ndim != 3:
        raise ValueError(
            f"fused_verify takes (b, k+1, V) logits; got {logits.shape}")
    b, k1, V = logits.shape
    if drafted.ndim != 2 or drafted.shape != (b, k1 - 1):
        raise ValueError(
            f"drafted must be (b={b}, k={k1 - 1}) to match the (b, k+1, "
            f"V) logits; got {drafted.shape}")
    if k1 < 2:
        raise ValueError(
            f"fused_verify needs k >= 1 drafted tokens (k+1 = {k1} logit "
            f"rows); a 1-row verify is just sampling — use fused_sample")
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    sampled = temperature > 0.0
    if sampled and key is None:
        raise ValueError(
            "temperature > 0 verification requires a PRNG key")
    # bonus row rides as NO_DRAFT: its accept flag is structurally False
    drafted_pad = jnp.concatenate(
        [drafted.astype(jnp.int32),
         jnp.full((b, 1), NO_DRAFT, jnp.int32)], axis=1)
    top_k = min(int(top_k), V)
    u_acc = u_gum = None
    if sampled:
        ka, kg = jax.random.split(key)
        tiny = jnp.finfo(jnp.float32).tiny  # (0, 1]: log(u) stays finite
        u_acc = jax.random.uniform(ka, (b, k1), jnp.float32, minval=tiny,
                                   maxval=1.0)
        u_gum = jax.random.uniform(kg, (b, k1, V), jnp.float32,
                                   minval=tiny, maxval=1.0)
    ok = verify_kernel_ok(k1, V, logits.dtype)
    if _backend.choose_impl(impl, ok) == "pallas":
        return fused_verify_fwd(
            logits, _col(drafted_pad), _col(u_acc), u_gum,
            temperature=float(temperature), top_k=top_k,
            top_p=float(top_p), interpret=_backend.interpret_mode())
    if sampled:
        return _cells(*verify_sampled(
            logits, _col(drafted_pad), _col(u_acc), u_gum,
            temperature=float(temperature), top_k=top_k,
            top_p=float(top_p)))
    return _cells(*verify_greedy(logits, _col(drafted_pad)))


def fused_verify_tree(logits: jax.Array, tokens: jax.Array,
                      parents: jax.Array, anc: jax.Array,
                      key: Optional[jax.Array] = None, *,
                      temperature: float = 0.0, top_k: int = 0,
                      top_p: float = 1.0, impl: str = "auto"
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Verify a DRAFT TREE of N tokens against N+1 target logit rows.

    ``logits`` (b, N+1, V): row j is the target's distribution for the
    token AFTER node j's token — row 0 after the committed pending
    token (the tree's root), rows 1..N after the drafted nodes.
    ``tokens`` (b, N+1) int32 node tokens (column 0 is the pending
    token and is ignored — it is pinned to ``NO_DRAFT`` internally);
    ``parents`` (b, N+1) int32 parent pointers into the same node
    index space (``parents[:, 0] == 0``, ``parents[:, j] < j`` — a
    topological order the drafters emit by construction); ``anc``
    (b, N+1, N+1) int32 ancestor-or-self closure (``anc[:, i, j] == 1``
    iff node j lies on node i's root path, node 0 and i included —
    :class:`apex_tpu.spec.tree.DraftTree` precomputes it once per
    static topology, so it ships as constant operand contents).

    Returns ``(accept_len (b,), j_star (b,), next_token (b,))`` int32:
    the deepest fully-accepted root path's length (accepted drafted
    tokens), its terminal node index, and the bonus/corrected token
    sampled from that node's row — one tree round emits the path's
    tokens plus ``next_token``, between 1 and depth+1 tokens. At
    branching 1 the semantics degenerate to :func:`fused_verify` (the
    chain is the one-branch tree). ``temperature == 0`` is exact
    greedy acceptance (the tree stream is token-identical to
    non-speculative greedy decoding); ``temperature > 0`` applies the
    point-mass rejection rule edge-wise along every root path, with
    each correction row filtering ALL of its drafted children (the
    chain's single-child residual, generalized). Noise is drawn inside
    the caller's jit and shared between kernel and XLA fallback, so
    ``impl`` never changes the verdict.
    """
    if logits.ndim != 3:
        raise ValueError(
            f"fused_verify_tree takes (b, N+1, V) logits; got "
            f"{logits.shape}")
    b, n1, V = logits.shape
    if tokens.shape != (b, n1) or parents.shape != (b, n1):
        raise ValueError(
            f"tokens/parents must be (b={b}, N+1={n1}) to match the "
            f"(b, N+1, V) logits; got {tokens.shape} / {parents.shape}")
    if anc.shape != (b, n1, n1):
        raise ValueError(
            f"anc must be the (b={b}, N+1={n1}, N+1={n1}) "
            f"ancestor-or-self closure; got {anc.shape}")
    if n1 < 2:
        raise ValueError(
            f"fused_verify_tree needs N >= 1 drafted nodes (N+1 = {n1} "
            f"logit rows); a 1-row verify is just sampling — use "
            f"fused_sample")
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    sampled = temperature > 0.0
    if sampled and key is None:
        raise ValueError(
            "temperature > 0 tree verification requires a PRNG key")
    # the root row carries no draft: its accept flag is structurally
    # irrelevant (tree_accepted_path forces node 0 accepted) but a
    # pinned NO_DRAFT keeps it out of the children filter
    tokens = tokens.astype(jnp.int32).at[:, 0].set(NO_DRAFT)
    parents = parents.astype(jnp.int32)
    anc = anc.astype(jnp.int32)
    top_k = min(int(top_k), V)
    u_acc = u_gum = None
    if sampled:
        ka, kg = jax.random.split(key)
        tiny = jnp.finfo(jnp.float32).tiny
        u_acc = jax.random.uniform(ka, (b, n1), jnp.float32, minval=tiny,
                                   maxval=1.0)
        u_gum = jax.random.uniform(kg, (b, n1, V), jnp.float32,
                                   minval=tiny, maxval=1.0)
    ok = verify_kernel_ok(n1, V, logits.dtype)
    if _backend.choose_impl(impl, ok) == "pallas":
        return fused_verify_tree_fwd(
            logits, _col(tokens), _col(parents), anc, _col(u_acc), u_gum,
            temperature=float(temperature), top_k=top_k,
            top_p=float(top_p), interpret=_backend.interpret_mode())
    if sampled:
        return _cells(*verify_tree_sampled(
            logits, _col(tokens), _col(parents), anc, _col(u_acc), u_gum,
            temperature=float(temperature), top_k=top_k,
            top_p=float(top_p)))
    return _cells(*verify_tree_greedy(logits, _col(tokens), _col(parents),
                                      anc))
