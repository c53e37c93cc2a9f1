"""Mixture-of-experts FFN with expert parallelism (TPU-first extension).

The reference has no MoE (SURVEY.md §2.3 lists EP as an absent strategy);
this fills the gap the TPU way: GShard/Switch-style static-capacity routing
expressed as einsums (XLA sees only fixed shapes — no ragged dispatch), with
experts sharded over a mesh axis and tokens exchanged by two
``lax.all_to_all``s, the same pattern Ulysses attention uses for heads.

Components:
* :func:`router_topk` — softmax gate + iterative top-k slot assignment with
  per-expert capacity, returning dense (tokens, E, C) dispatch/combine
  tensors; overflowing tokens are dropped (zero combine weight), underfull
  slots are zero-padded — both static-shape-friendly.
* :class:`MoEMLP` — per-expert two-layer FFN over the dispatched
  (E, C, d) blocks; batched einsum keeps every expert's GEMM on the MXU.
* :func:`moe_layer` — dispatch → (optional expert-parallel all_to_all) →
  experts → reverse all_to_all → combine; returns the output and the
  auxiliary losses (Switch load-balance, router z-loss).

Expert parallelism: run inside ``shard_map`` with ``axis_name`` bound (the
``dp`` axis by default — expert parallelism folds over data parallelism,
``apex_tpu.parallel.mesh.EXPERT_AXIS`` note). Each device hosts
``E // axis_size`` experts; the first all_to_all routes every device's
dispatched blocks to the experts' owners, the second routes results back.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from apex_tpu.monitor import spans as monitor_spans
from apex_tpu.ops import _backend
from apex_tpu.ops.pallas import expert_rows as rk
from apex_tpu.ops.pallas import grouped_matmul as gk
from apex_tpu.ops.pallas import top_rounds as tr


# the router aux schema — THE definition every consumer zero-initializes
# from (gpt.hidden_states_with_aux, GPTPipeline._stage accumulate against
# this exact tree structure)
ROUTER_AUX_ZEROS = {"load_balance_loss": 0.0, "router_z_loss": 0.0,
                    "drop_fraction": 0.0}


def router_aux_zeros(dtype=None):
    """Fresh init tree matching :func:`router_topk_sparse`'s aux output."""
    return jax.tree.map(
        lambda v: jnp.full((), v, dtype or jnp.float32), ROUTER_AUX_ZEROS)


def router_topk_sparse(
    logits: jax.Array,
    capacity: int,
    k: int = 2,
    *,
    normalize_gates: bool = True,
    priority: str = "gate",
) -> Tuple[jax.Array, jax.Array, dict]:
    """Top-k token→expert assignment with capacity, SPARSE form.

    ``logits``: (T, E). Returns ``(slot_ids, gates, aux)``:

    * ``slot_ids`` (k, T) int32 — round r assigns token t to flat expert
      slot ``e·C + c``; dropped (over-capacity) assignments point at the
      sentinel slot ``E·C`` (a dump row the dispatch scatter writes into
      and the combine gather zero-weights);
    * ``gates`` (k, T) fp32 — the (optionally renormalized) combine
      weights, 0 for dropped assignments;
    * ``aux`` — ``load_balance_loss`` (Switch-style: E · Σ_e fraction_e ·
      mean-gate_e, 1.0 at uniform routing), ``router_z_loss``, and
      ``drop_fraction`` (share of the T·k assignments that overflowed —
      surfaced so training loops can alarm on routing collapse).

    The sparse form is what :func:`moe_layer` consumes: dispatch/combine
    become an O(T·d) row scatter/gather instead of the GShard one-hot
    einsum whose (T, E, C) tensors are quadratic in tokens — at the
    flagship scale (T=16k, E=8) those weigh 2.7 GB each and cost 5× the
    expert FFN's own FLOPs (measured OOM, PERF.md r3). Use
    :func:`router_topk` when the dense masks themselves are wanted.

    Slot assignment is k rounds of argmax with chosen gates masked out.
    ``priority`` decides who wins a full expert's last slots within a
    round: ``"gate"`` (default) ranks claimants by router confidence —
    the GShard/V-MoE "important tokens first" rule, removing the
    position-in-batch bias — while ``"token"`` keeps raw batch order (the
    Switch formulation; deterministic and marginally cheaper — no sort).
    All shapes static either way.
    """
    if priority not in ("gate", "token"):
        raise ValueError(f"priority must be gate|token, got {priority!r}")
    T, E = logits.shape
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    remaining = gates
    counts = jnp.zeros((E,), jnp.int32)
    gate_sum = jnp.zeros((T,), jnp.float32)
    first_choice = None
    dropped = jnp.zeros((), jnp.float32)
    slot_ids = []
    gate_rounds = []

    for _ in range(k):
        choice = jnp.argmax(remaining, axis=-1)                    # (T,)
        onehot = jax.nn.one_hot(choice, E, dtype=jnp.float32)      # (T, E)
        if first_choice is None:
            first_choice = onehot
        gate_round = jnp.sum(gates * onehot, axis=-1)              # (T,)
        if priority == "gate":
            # rank claimants by gate value: the slot cumsum runs in
            # confidence order, then scatters back to token order
            order = jnp.argsort(-gate_round)
            oh_sorted = onehot[order]
            pos = (jnp.cumsum(oh_sorted, axis=0) - 1.0) + counts[None, :]
            slot_sorted = jnp.sum(pos * oh_sorted, axis=-1)
            slot = jnp.zeros((T,), slot_sorted.dtype).at[order].set(
                slot_sorted)
        else:
            pos = (jnp.cumsum(onehot, axis=0) - 1.0) + counts[None, :]
            slot = jnp.sum(pos * onehot, axis=-1)                  # (T,)
        fits = slot < capacity
        flat = choice.astype(jnp.int32) * capacity + slot.astype(jnp.int32)
        slot_ids.append(jnp.where(fits, flat, E * capacity))
        gate_val = gate_round * fits                               # (T,)
        gate_rounds.append(gate_val)
        gate_sum = gate_sum + gate_val
        counts = counts + jnp.sum(onehot * fits[:, None], axis=0).astype(jnp.int32)
        remaining = remaining * (1.0 - onehot)                     # mask chosen
        dropped = dropped + jnp.sum(1.0 - fits)

    gates_out = jnp.stack(gate_rounds)                             # (k, T)
    if normalize_gates:
        gates_out = gates_out / jnp.maximum(gate_sum, 1e-9)[None, :]

    # Switch load balance over the FIRST choice (the dominant assignment):
    # fraction of tokens routed to e x mean router prob for e, scaled by E.
    frac = jnp.mean(first_choice, axis=0)
    prob = jnp.mean(gates, axis=0)
    aux = {
        "load_balance_loss": E * jnp.sum(frac * prob),
        "router_z_loss": jnp.mean(jax.nn.logsumexp(
            logits.astype(jnp.float32), axis=-1) ** 2),
        "drop_fraction": dropped / float(T * k),
    }
    return jnp.stack(slot_ids), gates_out, aux


def router_topk(
    logits: jax.Array,
    capacity: int,
    k: int = 2,
    *,
    normalize_gates: bool = True,
    priority: str = "gate",
) -> Tuple[jax.Array, jax.Array, dict]:
    """Dense (GShard-mask) form of :func:`router_topk_sparse`: returns
    ``(dispatch (T, E, C) one-hot, combine (T, E, C) gate-weighted, aux)``.
    O(T·E·C) memory — fine for tests/small routing, quadratic in tokens at
    scale (prefer the sparse form `moe_layer` uses)."""
    T, E = logits.shape
    slot_ids, gates, aux = router_topk_sparse(
        logits, capacity, k, normalize_gates=normalize_gates,
        priority=priority)
    dispatch = jnp.zeros((T, E * capacity + 1), jnp.float32)
    combine = jnp.zeros((T, E * capacity + 1), jnp.float32)
    rows = jnp.arange(T)
    for r in range(slot_ids.shape[0]):
        dispatch = dispatch.at[rows, slot_ids[r]].add(1.0)
        combine = combine.at[rows, slot_ids[r]].add(gates[r])
    return (dispatch[:, :-1].reshape(T, E, capacity),
            combine[:, :-1].reshape(T, E, capacity), aux)


def slot_ids_are_unique(slot_ids, num_slots) -> jax.Array:
    """Debug invariant behind :func:`_slot_inverse` and the gather
    dispatch/combine VJPs: every real (< ``num_slots``) slot id appears AT
    MOST ONCE across all k rounds. :func:`router_topk_sparse` guarantees it
    (the per-expert slot cumsum carries ``counts`` across rounds, so two
    assignments can never land on the same (expert, position)); a future
    router emitting duplicates would silently drop tokens in the
    ``mode='drop'`` scatters and corrupt the hand-written VJPs. Returns a
    traced bool — assert it in tests / under a debug flag whenever the
    routing logic changes (tests/test_moe.py::TestRouter does)."""
    flat = slot_ids.reshape(-1)
    counts = jnp.zeros((num_slots + 1,), jnp.int32).at[
        jnp.clip(flat, 0, num_slots)].add(1)
    return jnp.all(counts[:num_slots] <= 1)


def _slot_inverse(slot_ids, gates, num_slots):
    """Invert the token→slot assignment: slot ids are UNIQUE across rounds
    (the slot cumsum carries counts over), so the (T, d) dispatch scatter is
    a permutation — invertible into (S,)-sized scalar scatters that cost
    1/512th of the row scatter they replace. Returns (inv (S,) int32 —
    which token fills each slot, valid (S,) bool — empty slots must
    contribute zeros). The per-slot gate value is NOT built here:
    `_gather_combine_bwd` derives it from its own residuals, keeping the
    inversion-by-scatter logic in exactly one consumer per quantity."""
    k, T = slot_ids.shape
    del gates
    inv = jnp.zeros((num_slots,), jnp.int32)
    valid = jnp.zeros((num_slots,), jnp.bool_)
    tok = jnp.arange(T, dtype=jnp.int32)
    for r in range(k):
        sid = slot_ids[r]  # dump assignments (== num_slots) drop out of range
        inv = inv.at[sid].set(tok, mode="drop")
        valid = valid.at[sid].set(True, mode="drop")
    return inv, valid


@jax.custom_vjp
def _gather_dispatch(xt, slot_ids, inv, valid):
    """(T, d) tokens → (S, d) expert slots, as a row GATHER both ways.

    The obvious formulation — ``buf.at[slot_ids].add(xt)`` — is an XLA row
    scatter, and its transpose (plus the remat re-forward) made the
    dispatch/combine pair cost ~62 ms/step at the flagship MoE shape
    (PERF.md r3): TPU scatters neither fuse nor pipeline the way gathers
    do. With the slot inverse precomputed, forward is ``xt[inv]`` masked by
    slot validity, and the hand-written VJP routes the cotangent back with
    the forward's own ``slot_ids`` gather — no (T, d)-sized scatter exists
    in either direction."""
    return jnp.where(valid[:, None], xt[inv], 0).astype(xt.dtype)


def _gather_dispatch_fwd(xt, slot_ids, inv, valid):
    return _gather_dispatch(xt, slot_ids, inv, valid), (slot_ids, inv.shape)


def _gather_dispatch_bwd(res, g):
    import numpy as np
    slot_ids, inv_shape = res
    gp = jnp.concatenate([g, jnp.zeros((1, g.shape[1]), g.dtype)], 0)
    dxt = gp[slot_ids[0]]
    for r in range(1, slot_ids.shape[0]):
        dxt = dxt + gp[slot_ids[r]]
    f0 = lambda s: np.zeros(s, jax.dtypes.float0)  # noqa: E731
    return dxt, f0(slot_ids.shape), f0(inv_shape), f0(inv_shape)


_gather_dispatch.defvjp(_gather_dispatch_fwd, _gather_dispatch_bwd)


@jax.custom_vjp
def _gather_combine(op, gates, slot_ids, inv, valid):
    """y_t = Σ_r gates_r(t) · op[slot_r(t)] with the dump row synthesized as
    a zero row; the VJP's d_op is a gather by ``inv`` (the scatter-free
    mirror of :func:`_gather_dispatch`)."""
    opp = jnp.concatenate([op, jnp.zeros((1, op.shape[1]), op.dtype)], 0)
    y = gates[0][:, None].astype(opp.dtype) * opp[slot_ids[0]]
    for r in range(1, gates.shape[0]):
        y = y + gates[r][:, None].astype(opp.dtype) * opp[slot_ids[r]]
    return y


def _gather_combine_fwd(op, gates, slot_ids, inv, valid):
    return (_gather_combine(op, gates, slot_ids, inv, valid),
            (op, gates, slot_ids, inv, valid))


def _gather_combine_bwd(res, dy):
    import numpy as np
    op, gates, slot_ids, inv, valid = res
    S = op.shape[0]
    gates_slot = jnp.zeros((S,), jnp.float32)
    for r in range(gates.shape[0]):
        gates_slot = gates_slot.at[slot_ids[r]].set(gates[r], mode="drop")
    d_op = (jnp.where(valid, gates_slot, 0.0)[:, None]
            * dy.astype(jnp.float32)[inv]).astype(op.dtype)
    opp = jnp.concatenate([op, jnp.zeros((1, op.shape[1]), op.dtype)], 0)
    dyf = dy.astype(jnp.float32)
    d_gates = jnp.stack([
        jnp.sum(dyf * opp[slot_ids[r]].astype(jnp.float32), axis=-1)
        for r in range(gates.shape[0])])
    f0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa: E731
    return d_op, d_gates, f0(slot_ids), f0(inv), f0(valid)


_gather_combine.defvjp(_gather_combine_fwd, _gather_combine_bwd)


@dataclasses.dataclass
class MoEMLP:
    """Per-expert FFN bank (num_experts_local, hidden, ffn) — GEMMs stay
    batched over experts so the MXU sees (E·C, hidden) x (hidden, ffn).

    ``tp_size > 1``: each expert's FFN is tensor-parallel over its ffn dim
    (w1 column-sharded, w2 row-sharded — the same Col→Row split the dense
    ``ParallelMLP`` uses, reference ``standalone_gpt.py:236``); b2 is
    replicated and added after the tp reduce. Composes orthogonally with
    expert parallelism: ep shards *which experts* a device owns, tp shards
    *each expert's* GEMMs."""

    num_experts: int
    hidden: int
    ffn: int
    tp_size: int = 1

    @property
    def ffn_per_partition(self) -> int:
        if self.ffn % self.tp_size:
            raise ValueError(
                f"ffn ({self.ffn}) must be divisible by tp_size "
                f"({self.tp_size}) for tensor-parallel experts")
        return self.ffn // self.tp_size

    def init(self, key, rank: int = 0, dtype=jnp.float32):
        """This tp rank's shard. The full (tp=1) bank is generated and
        sliced so a per-rank init equals the corresponding slice of a
        replicated init (the ``shard_params_for_tp`` contract)."""
        k1, k2, k3 = jax.random.split(key, 3)
        s1 = (2.0 / self.hidden) ** 0.5
        s2 = (2.0 / self.ffn) ** 0.5
        fp = self.ffn_per_partition
        sl = slice(rank * fp, (rank + 1) * fp)
        w1 = jax.random.normal(
            k1, (self.num_experts, self.hidden, self.ffn), dtype) * s1
        w2 = jax.random.normal(
            k2, (self.num_experts, self.ffn, self.hidden), dtype) * s2
        return {
            "router": jax.random.normal(k3, (self.hidden, self.num_experts), dtype) * 0.02,
            "w1": w1[:, :, sl],
            "b1": jnp.zeros((self.num_experts, fp), dtype),
            "w2": w2[:, sl, :],
            "b2": jnp.zeros((self.num_experts, self.hidden), dtype),
        }


def _expert_ffn(params, x_ecd, tp_axis=None):
    """(E_local, C', d) through each expert's two-layer GELU FFN. With
    ``tp_axis`` the ffn dim is sharded over it: the input enters through
    copy-to-region (identity fwd, psum bwd) and the partial products leave
    through reduce-from-region (psum fwd, identity bwd) — the Megatron
    Col→Row collective placement, expert-batched."""
    from apex_tpu.transformer.tensor_parallel import mappings
    x_ecd = mappings.copy_to_tensor_model_parallel_region(x_ecd, tp_axis)
    h = jnp.einsum("ecd,edf->ecf", x_ecd, params["w1"]) + params["b1"][:, None, :]
    h = jax.nn.gelu(h, approximate=True)
    y = jnp.einsum("ecf,efd->ecd", h, params["w2"])
    y = mappings.reduce_from_tensor_model_parallel_region(y, tp_axis)
    return y + params["b2"][:, None, :]


def moe_layer(
    params: dict,
    x: jax.Array,
    *,
    k: int = 2,
    capacity_factor: float = 1.25,
    axis_name: Optional[str] = None,
    tp_axis: Optional[str] = None,
    normalize_gates: bool = True,
    priority: str = "gate",
) -> Tuple[jax.Array, dict]:
    """MoE FFN over ``x`` (..., hidden); returns (y, aux_losses —
    including ``drop_fraction``, see :func:`router_topk`).

    With ``axis_name`` (inside shard_map): experts are sharded over the
    axis — ``params['w1']`` etc. hold this device's ``E_local`` experts and
    the router logits cover all ``E_local · axis_size`` experts. Dispatched
    blocks take one ``all_to_all`` to the expert owners and one back.

    With ``tp_axis``: each expert's ffn dim is additionally sharded over
    that axis (see :class:`MoEMLP`); routing/dispatch/combine run
    replicated across tp — only the expert GEMMs split.
    """
    lead = x.shape[:-1]
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    T = xt.shape[0]

    ep = jax.lax.axis_size(axis_name) if axis_name else 1
    e_local = params["w1"].shape[0]
    E = e_local * ep
    if params["router"].shape[-1] != E:
        raise ValueError(
            f"router covers {params['router'].shape[-1]} experts but the "
            f"expert bank holds {e_local} x axis size {ep} = {E}")
    capacity = max(1, int(capacity_factor * k * T / E))

    logits = xt.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    slot_ids, gates, aux = router_topk_sparse(
        logits, capacity, k, normalize_gates=normalize_gates,
        priority=priority)

    # Dispatch/combine as row GATHERS in both directions (forward AND
    # cotangent): slot uniqueness makes the assignment a permutation, so
    # the slot→token inverse turns the O(T·d) row scatter — and the
    # scatter its transpose would emit — into gathers (custom VJPs above;
    # the scatter formulation cost ~62 ms/step at flagship MoE scale).
    # The GShard one-hot einsum both replace materialized (T, E, C) masks
    # — quadratic in tokens and 5× the expert FFN's own FLOPs (PERF.md r3).
    inv, valid = _slot_inverse(slot_ids, gates, E * capacity)
    expert_in = _gather_dispatch(xt, slot_ids, inv, valid
                                 ).reshape(E, capacity, d)

    if axis_name:
        # (E, C, d) -> (ep, e_local, C, d) -> a2a -> (e_local, ep*C, d):
        # each device gathers every peer's blocks for ITS experts
        blocks = expert_in.reshape(ep, e_local, capacity, d)
        blocks = jax.lax.all_to_all(blocks, axis_name, split_axis=0,
                                    concat_axis=2, tiled=True)
        out = _expert_ffn(params, blocks.reshape(e_local, ep * capacity, d),
                          tp_axis)
        out = out.reshape(1, e_local, ep * capacity, d)
        out = jax.lax.all_to_all(out, axis_name, split_axis=2,
                                 concat_axis=0, tiled=True)
        expert_out = out.reshape(E, capacity, d)
    else:
        expert_out = _expert_ffn(params, expert_in, tp_axis)

    y = _gather_combine(expert_out.reshape(E * capacity, d), gates,
                        slot_ids, inv, valid)
    return y.reshape(*lead, d).astype(x.dtype), aux


# --- dropless routing onto the experts held here -----------------------------
#
# The capacity layer above pads or drops to a fixed (E, C) grid. The layer
# below drops nothing: every (token, expert) assignment whose expert is held
# here is computed, however uneven the routing. Static shapes come from
# counting the assignments by expert into a row buffer in which each expert's
# rows start on a tile boundary (``ops/pallas/grouped_matmul``): a row's place
# is its expert's first row plus the earlier assignments to that expert, so
# nothing is sorted (this compiler lowers ``top_k`` and ``argsort`` alike to
# full stable sorts, and a gather or a scatter costs it some 5 ns an element:
# the plan keeps two scatters of the T k assignments and no gather). The rows
# are computed a block at a time (``dropless_block_rows``: as many rows as
# there are tokens where a rank holds a small share of the experts), as many
# blocks as the routing fills. A block is a unit of memory, not of cost: the
# movements between tokens and rows (``ops/pallas/expert_rows``) and the
# grouped products stop at the tiles in use.

@jax.custom_vjp
def _scores_at(p, ids, lane):
    """``p`` (T, E) at ``ids`` (T, k), ``lane = arange(E)``: a select over
    (T, k, E) and its sum, forward and (onto (T, E)) backward, from the ids
    alone. XLA's gather of T k scalars takes many times as long here, and the
    scatter-add that transposes it compiles to a sort of the T k indices."""
    return jnp.sum(jnp.where(ids[..., None] == lane, p[:, None, :], 0), axis=-1)


def _scores_at_fwd(p, ids, lane):
    return _scores_at(p, ids, lane), (ids, lane)


def _scores_at_bwd(res, g):
    ids, lane = res
    return jnp.sum(jnp.where(ids[..., None] == lane, g[..., None], 0), axis=1), _f0(ids), _f0(lane)


_scores_at.defvjp(_scores_at_fwd, _scores_at_bwd)


def kept_groups(biased, groups, kept):
    """Which of a row's ``groups`` groups of consecutive experts stay, (T,
    groups) bool: a group's score is the sum of its two largest entries of
    ``biased`` (T, E), the ``kept`` best groups stay, the lowest index among
    equals (:func:`top_rounds.top_rounds` both times)."""
    T, E = biased.shape
    per = biased.reshape(T * groups, E // groups)
    best = jnp.sum(jnp.where(tr.top_rounds(per, 2)[..., None] == jnp.arange(E // groups),
                             per[:, None, :], 0.0), axis=(1, 2)).reshape(T, groups)
    ids = tr.top_rounds(best, kept)
    return jnp.any(ids[..., None] == jnp.arange(groups), axis=1)


def route_topk(x, router, k, *, normalize=True, score="softmax", bias=None, scale=1.0,
               sequences=None, impl="auto", groups=1, groups_kept=1, with_kept=False):
    """Router at its full width: ``p = softmax_fp32(x @ router)``, the top
    ``k`` (ids (T, k) int32, weights (T, k) float32, renormalised to sum 1
    when ``normalize``), the Switch load-balance term ``E sum_e f_e P_e``
    (``f``: share of the T k assignments, ``P``: mean probability) and the
    assignments to every expert (E,) int32.

    The top ``k`` are chosen by k rounds of a row maximum, the lowest index
    among equals (``ops/pallas/top_rounds``: ``jax.lax.top_k``'s ids without
    its sort; the kernel ``moe_top_rounds`` or, ``impl="xla"`` and off the
    chip, the same rounds as XLA operations). The ids carry no gradient; the
    weights are ``p`` at them (:func:`_scores_at`).

    ``score="sigmoid"``: every expert is scored alone, ``p = sigmoid``; the
    renormalisation guards an all-zero row (``+ 1e-20``) and the term is 0
    (such a router is balanced by ``bias``, not by a loss). ``bias`` (E,)
    float32 enters the choice of the top ``k`` and not their weights, and
    carries no gradient (:func:`router_bias_update` moves it). ``scale``
    multiplies the weights last.

    ``groups`` > 1: the choice is group-limited (:func:`kept_groups`). The E
    experts are ``groups`` groups of consecutive ones; on ``p + bias`` — the
    ``bias`` enters the groups' scores as it enters the choice — a token keeps
    its ``groups_kept`` best groups and its top ``k`` are taken inside them.
    ``with_kept`` also returns which groups every token kept, (T, groups)
    bool. 1 and 1: the choice over the whole row, as it was.

    ``sequences`` (int; the T tokens are that many rows of T / sequences):
    the balance term taken per sequence and averaged over them — for each
    row ``E sum_e f_e P_e`` with ``f`` the share of the row's assignments
    and ``P`` the row's mean probability (DeepSeek's ``seq_aux``). ``None``:
    the batch-wise term above."""
    logits = jnp.dot(x, router.astype(x.dtype), preferred_element_type=jnp.float32)
    p = jax.nn.softmax(logits, axis=-1) if score == "softmax" else jax.nn.sigmoid(logits)
    E = router.shape[-1]
    lane = jnp.arange(E, dtype=jnp.int32)
    chosen_by = jax.lax.stop_gradient(p)
    ok = tr.shapes_ok(x.shape[0], E)
    offset = jnp.zeros((E,), jnp.float32) if bias is None else bias.astype(jnp.float32)
    kept = None
    if groups > 1:             # the other groups' experts leave the choice
        kept = kept_groups(chosen_by + offset, groups, groups_kept)
        chosen_by = jnp.where(jnp.repeat(kept, E // groups, axis=1), chosen_by + offset, -jnp.inf)
        offset = jnp.zeros((E,), jnp.float32)
    if _backend.choose_impl(impl if ok else "xla", ok) == "pallas":
        top_e = tr.moe_top_rounds(chosen_by.T, offset, k=k, interpret=_backend.interpret_mode()).T
    else:
        top_e = tr.top_rounds(chosen_by + offset, k)
    # part of the plan a jax.checkpoint policy may keep (``moe_plan``): the
    # backward pass then reads the weights again and runs no round twice
    top_e = checkpoint_name(top_e, "moe_plan")
    top_p = _scores_at(p, top_e, lane)
    if normalize:
        total = jnp.sum(top_p, -1, keepdims=True)
        top_p = top_p / (total if score == "softmax" else total + 1e-20)
    if scale != 1.0:
        top_p = top_p * scale
    chosen = top_e[..., None] == lane
    aux = jnp.float32(0.0)
    if sequences is None:
        counts = jnp.sum(chosen, axis=(0, 1), dtype=jnp.int32)
        if score == "softmax":
            share = counts.astype(jnp.float32) / (x.shape[0] * k)
            aux = E * jnp.sum(share * jnp.mean(p, axis=0))
    else:
        by_row = lambda a: a.reshape(sequences, -1, *a.shape[1:])  # noqa: E731
        row_counts = jnp.sum(by_row(chosen), axis=(1, 2), dtype=jnp.int32)
        counts = jnp.sum(row_counts, axis=0)
        if score == "softmax":
            share = row_counts.astype(jnp.float32) / (x.shape[0] // sequences * k)
            aux = E * jnp.mean(jnp.sum(share * jnp.mean(by_row(p), axis=1), axis=-1))
    return (top_e, top_p, aux, counts, kept) if with_kept else (top_e, top_p, aux, counts)


def router_bias_update(bias, counts, rate):
    """The balancing step of a router that carries a selection bias and no
    auxiliary loss: ``b + rate * sign(mean(n) - n)`` over the step's
    assignments ``n`` (..., E) to every expert — an expert under the mean is
    made likelier to be chosen, one over it less. Pure; the training step
    keeps ``bias`` as state beside its parameters."""
    n = counts.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(n, axis=-1, keepdims=True) - n)


def dropless_plan(top_e, counts, experts_held, block_rows, tile):
    """Where each local assignment's row lives, for the most rows a routing
    can fill (every token's assignments local, every expert's last tile
    nearly empty) in whole blocks of ``block_rows``. Returns a dict of int32
    / bool arrays: ``tile_expert`` (tiles,) the held expert of every tile,
    ``n_used`` () tiles in use, ``row_token``, ``row_assign``, ``row_valid``
    (rows,), ``pos`` (T, k) the row of each assignment, ``local`` (T, k);
    and, for the way back, the local assignments as they stand in token
    order: ``tile_rows`` (T / TT, L) the rows that each tile of
    ``expert_rows.TT`` tokens sums, ``tile_count`` how many, ``rank`` (T, k)
    each assignment's place in its tile's list (-1: not local; T rounded up
    to whole tiles in all three).

    The rows are those of a stable sort of the assignments by expert, made by
    counting: an assignment's place among its expert's rows is how many
    earlier assignments (in the order t k + j) chose that expert — the
    earlier tokens' (a histogram a token, summed along the tokens) and the
    same token's. Assignments to experts held elsewhere are never ordered:
    their ``pos`` reads 0 under a ``local`` that is false, and a row that
    ``row_valid`` excludes reads assignment 0."""
    first, count = experts_held
    T, k = top_e.shape
    N = T * k
    worst = T * min(k, count) + count * tile
    rows = -(-worst // block_rows) * block_rows
    local = (top_e >= first) & (top_e < first + count)
    held = jax.lax.dynamic_slice(counts, (first,), (count,))
    tiles_of = -(-held // tile)
    tile_end = jnp.cumsum(tiles_of)
    tile_start = tile_end - tiles_of
    n_used = tile_end[-1]
    # by tile, then spread over the tile's rows: a gather a row costs as much as the rows do
    i = jnp.arange(-(-rows // tile), dtype=jnp.int32)      # a last tile cut short is never in use
    tile_expert = jnp.minimum(jnp.sum(i[:, None] >= tile_end, axis=1, dtype=jnp.int32), count - 1)
    filled = jnp.where(i < n_used,
                       held[tile_expert] - (i - tile_start[tile_expert]) * tile, 0)
    row_valid = (jnp.arange(tile, dtype=jnp.int32) < filled[:, None]).reshape(-1)[:rows]
    tile_expert = tile_expert[:rows // tile]
    key = jnp.where(local, top_e - first, count)              # count: held elsewhere
    chose = key[..., None] == jnp.arange(count, dtype=key.dtype)            # (T, k, count)
    per_token = jnp.sum(chose, axis=1, dtype=jnp.int32)
    earlier = jnp.cumsum(per_token, axis=0) - per_token + tile_start * tile   # (T, count)
    lower = jnp.tril(jnp.ones((k, k), bool), -1)               # [j, j']: j' before j
    twice = jnp.sum((key[:, :, None] == key[:, None, :]) & lower, axis=-1, dtype=jnp.int32)
    pos = jnp.where(local, jnp.sum(jnp.where(chose, earlier[:, None, :], 0), axis=-1) + twice, 0)
    row_assign = jnp.zeros((rows,), jnp.int32).at[jnp.where(local, pos, rows).reshape(N)].set(
        jnp.arange(N, dtype=jnp.int32), mode="drop", unique_indices=True)
    # the token-ordered lists, counted the same way: the tile's earlier tokens, then the token's own
    token_tiles = -(-T // rk.TT)
    padded = lambda a: jnp.pad(a, ((0, token_tiles * rk.TT - T), (0, 0)))  # noqa: E731
    listed = padded(local)
    mine = jnp.sum(listed, axis=1, dtype=jnp.int32).reshape(token_tiles, rk.TT)
    upto = jnp.cumsum(mine, axis=1)
    rank = jnp.where(listed, (upto - mine).reshape(-1, 1)
                     + jnp.sum(listed[:, None, :] & lower, axis=-1, dtype=jnp.int32), -1)
    length = rk.list_length(min(k, count))
    tile_of = jnp.arange(token_tiles * rk.TT, dtype=jnp.int32)[:, None] // rk.TT
    slot = jnp.where(listed, tile_of * length + rank, token_tiles * length)
    tile_rows = jnp.zeros((token_tiles * length,), jnp.int32).at[slot.reshape(-1)].set(
        padded(pos).reshape(-1), mode="drop", unique_indices=True)
    return {"tile_expert": tile_expert, "n_used": n_used.astype(jnp.int32),
            "row_token": row_assign // k, "row_assign": row_assign,
            "row_valid": row_valid, "pos": pos, "local": local,
            "tile_rows": tile_rows.reshape(token_tiles, length), "tile_count": upto[:, -1],
            "rank": rank}


def _f0(a):
    import numpy as np
    return np.zeros(a.shape, jax.dtypes.float0)


def _rows_impl(impl, a):
    """The movements' implementation: the row kernels at every width they
    take (``expert_rows.shapes_ok``), XLA's movements at the others under
    ``impl="pallas"`` too."""
    ok = rk.shapes_ok(a.shape[-1], a.dtype, _backend.interpret_mode())
    return _backend.choose_impl(impl if ok else "xla", ok)


def _gather_rows(x, move, scale, dot_with=None):
    """``moe_rows_gather`` over one block: row r takes ``scale[r]`` times the
    token ``row_token[r]`` (rows that ``row_valid`` excludes: a scale of 0)."""
    return rk.moe_rows_gather(
        rk.as_groups(x), move["row_token"], jnp.where(move["row_valid"], scale, 0.0),
        move["n_used"], dot_with, width=x.shape[-1], dtype=x.dtype,
        interpret=_backend.interpret_mode())


def _combine_rows(y, move, weights=None):
    """``moe_rows_combine`` over one block: every token sums the rows its
    selected assignments own, each times its weight (None: 1)."""
    tokens = move["pos"].shape[0]
    if weights is not None:
        weights = jnp.pad(weights.astype(jnp.float32),
                          ((0, move["rank"].shape[0] - tokens), (0, 0)))
    interpret = _backend.interpret_mode()
    return rk.moe_rows_combine(
        rk.moe_rows_pack(y, move["n_used"], interpret=interpret),
        move["tile_rows"], move["tile_count"], move["rank"], weights,
        tokens=tokens, width=y.shape[-1], dtype=y.dtype, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rows_from_tokens(x, move, impl):
    """(T, H) tokens -> this block's (R, H) rows: a gather, and a gather
    back (each token sums the rows its selected assignments own)."""
    if _rows_impl(impl, x) == "pallas":
        return _gather_rows(x, move, 1.0)
    return jnp.where(move["row_valid"][:, None], x[move["row_token"]], 0).astype(x.dtype)


def _rows_fwd(x, move, impl):
    return _rows_from_tokens(x, move, impl), move


def _rows_bwd(impl, move, g):
    if _rows_impl(impl, g) == "pallas":
        return _combine_rows(g, move), jax.tree.map(_f0, move)
    picked = jnp.where(move["sel"][..., None], g[move["pos"]], 0)             # (T, k, H)
    dx = jnp.sum(picked.astype(jnp.float32), axis=1).astype(g.dtype)
    return dx, jax.tree.map(_f0, move)


_rows_from_tokens.defvjp(_rows_fwd, _rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _tokens_from_rows(y, weights, move, impl):
    """out[t] = sum_k sel[t, k] weights[t, k] y[pos[t, k]] (float32 sum);
    its cotangent for ``y`` is again a gather, by ``row_token``."""
    if _rows_impl(impl, y) == "pallas":
        return _combine_rows(y, move, weights)
    picked = jnp.where(move["sel"][..., None], y[move["pos"]], 0).astype(jnp.float32)
    return jnp.einsum("tkh,tk->th", picked, weights).astype(y.dtype)


def _tokens_fwd(y, weights, move, impl):
    return _tokens_from_rows(y, weights, move, impl), (y, weights, move)


def _tokens_bwd(impl, res, dout):
    y, weights, move = res
    row_weight = weights.reshape(-1)[move["row_assign"]]
    if _rows_impl(impl, y) == "pallas":
        dy, dots = _gather_rows(dout.astype(y.dtype), move, row_weight, dot_with=y)
    else:
        d_row = jnp.where(move["row_valid"][:, None], dout[move["row_token"]], 0)  # (R, H)
        dy = (d_row.astype(jnp.float32) * row_weight[:, None]).astype(y.dtype)
        dots = jnp.sum(d_row.astype(jnp.float32) * y.astype(jnp.float32), axis=-1)
    dweights = jnp.where(move["sel"], dots[move["pos"]], 0.0).astype(weights.dtype)
    return dy, dweights, jax.tree.map(_f0, move)


_tokens_from_rows.defvjp(_tokens_fwd, _tokens_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(x, w, tile_expert, n_used, impl="auto"):
    """out[tile] = x[tile] @ w[tile_expert[tile]] over tiles of
    ``grouped_matmul.TM`` rows; tiles from ``n_used`` on give zeros. As the
    ``moe_gmm`` kernels or (``impl="xla"``) a batched einsum against the
    gathered matrices."""
    nu = n_used.reshape(1)
    if _backend.choose_impl(impl, _gmm_shapes_ok(x, w)) == "pallas":
        return gk.moe_gmm(x, w, tile_expert, nu, interpret=_backend.interpret_mode())
    tiles = x.reshape(-1, gk.TM, x.shape[-1])
    out = jnp.einsum("tmk,tkn->tmn", tiles, w[tile_expert],
                     preferred_element_type=jnp.float32)
    used = jnp.arange(tiles.shape[0]) < n_used
    return jnp.where(used[:, None, None], out, 0).astype(x.dtype).reshape(x.shape[0], -1)


def _gmm_shapes_ok(x, w):
    """Widths the grouped products take: every block is a whole matrix or a
    tile of whole rows, so a width need not be whole lane tiles (1,856 = 14.5
    of them runs on the kernels at its own width, nothing padded in HBM) —
    whole half tiles, as compiled and checked on the chip."""
    return x.shape[-1] % 64 == 0 and w.shape[-1] % 64 == 0


def _gmm_fwd(x, w, tile_expert, n_used, impl):
    return grouped_matmul(x, w, tile_expert, n_used, impl), (x, w, tile_expert, n_used)


def _gmm_bwd(impl, res, dy):
    x, w, tile_expert, n_used = res
    nu = n_used.reshape(1)
    if _backend.choose_impl(impl, _gmm_shapes_ok(x, w)) == "pallas":
        interpret = _backend.interpret_mode()
        dx = gk.moe_gmm_dx(dy, w, tile_expert, nu, interpret=interpret)
        dw = gk.moe_gmm_dw(x, dy, tile_expert, nu, w.shape[0], interpret=interpret)
    else:
        used = (jnp.arange(tile_expert.shape[0]) < n_used)[:, None, None]
        xt = x.reshape(-1, gk.TM, x.shape[-1])
        dyt = jnp.where(used, dy.reshape(-1, gk.TM, dy.shape[-1]), 0)
        dx = jnp.einsum("tmn,tkn->tmk", dyt, w[tile_expert],
                        preferred_element_type=jnp.float32).astype(x.dtype).reshape(x.shape)
        per_tile = jnp.einsum("tmk,tmn->tkn", xt, dyt, preferred_element_type=jnp.float32)
        dw = jax.ops.segment_sum(per_tile, tile_expert, num_segments=w.shape[0]).astype(w.dtype)
    return dx, dw, _f0(tile_expert), _f0(n_used)


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)


def silu_gate(h):
    """A SwiGLU's hidden from its fused ``gate|up`` product (..., 2 F):
    ``silu(gate) * up`` in float32, in ``h``'s dtype."""
    gate, up = jnp.split(h, 2, axis=-1)
    return (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(h.dtype)


def relu2(h):
    """An ungated feed-forward's hidden from its ``up`` product: ``relu(h)^2``
    in float32, in ``h``'s dtype."""
    r = jax.nn.relu(h.astype(jnp.float32))
    return (r * r).astype(h.dtype)


# an expert's hidden from its first product, by the activation's name:
# ``silu_gate`` reads a fused ``gate|up`` (..., 2 F), ``relu2`` one ``up`` (..., F)
ACTIVATIONS = {"silu_gate": silu_gate, "relu2": relu2}
# the leaves of that first product (the routed experts', the shared expert's)
# and how many times F they are wide
FIRST_LEAVES = {"silu_gate": ("w_gate_up", "shared_gate_up", 2),
                "relu2": ("w_up", "shared_up", 1)}


def _block_move(plan, block, rows):
    """The plan cut to the rows ``[block * rows, (block + 1) * rows)``: what
    the two movements and the grouped products of one block read."""
    lo = block * rows
    cut = lambda a: jax.lax.dynamic_slice_in_dim(a, lo, rows)  # noqa: E731
    sel = plan["local"] & (plan["pos"] >= lo) & (plan["pos"] < lo + rows)
    listed = jnp.pad(sel, ((0, plan["rank"].shape[0] - sel.shape[0]), (0, 0)))
    return {"tile_expert": jax.lax.dynamic_slice_in_dim(plan["tile_expert"], lo // gk.TM,
                                                        rows // gk.TM),
            "n_used": jnp.clip(plan["n_used"] - lo // gk.TM, 0, rows // gk.TM).reshape(1),
            "row_token": cut(plan["row_token"]), "row_assign": cut(plan["row_assign"]),
            "row_valid": cut(plan["row_valid"]), "sel": sel,
            "pos": jnp.clip(plan["pos"] - lo, 0, rows - 1),
            # the token-ordered lists hold every block's rows: for another block's,
            # this block's first row is fetched and weighs nothing
            "tile_rows": jnp.where((plan["tile_rows"] >= lo) & (plan["tile_rows"] < lo + rows),
                                   plan["tile_rows"] - lo, 0),
            "tile_count": plan["tile_count"], "rank": jnp.where(listed, plan["rank"], -1)}


def _held_experts_block(x, weights, w_gate_up, w_down, plan, block, rows, impl,
                        activation="silu_gate"):
    """What the rows ``[block * rows, (block + 1) * rows)`` of the plan add
    to every token: gather, gate/up (or up) product, the activation, down
    product, weighted gather back."""
    move = _block_move(plan, block, rows)
    tile_expert, n_used = move["tile_expert"], move["n_used"][0]
    xs = _rows_from_tokens(x, move, impl)
    with monitor_spans.span("moe/experts"):
        h = grouped_matmul(xs, w_gate_up, tile_expert, n_used, impl)
        y = grouped_matmul(ACTIVATIONS[activation](h), w_down, tile_expert, n_used, impl)
    return _tokens_from_rows(y, weights, move, impl)


def _add(a, b):
    return jax.tree.map(
        lambda a, b: (a.astype(jnp.float32) + b.astype(jnp.float32)).astype(a.dtype), a, b)


def _blocks_used(plan, rows):
    return -(-plan["n_used"] * gk.TM // rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _held_experts(x, weights, w_gate_up, w_down, plan, rows, impl, activation="silu_gate"):
    """What the experts held add to every token: the plan's rows, block after
    block of ``rows``, as many blocks as the routing fills — a loop whose
    length the routing decides. The first block always runs and keeps for
    the backward pass what differentiation would keep; a loop of unknown
    length can keep nothing, so the backward pass computes every further
    block again."""
    return _held_experts_fwd(x, weights, w_gate_up, w_down, plan, rows, impl, activation)[0]


def _held_experts_fwd(x, weights, w_gate_up, w_down, plan, rows, impl, activation):
    args = (x, weights, w_gate_up, w_down)
    one = lambda b: functools.partial(  # noqa: E731
        _held_experts_block, plan=plan, block=b, rows=rows, impl=impl, activation=activation)
    y, pull_first = jax.vjp(one(0), *args)
    y = jax.lax.fori_loop(1, _blocks_used(plan, rows),
                          lambda b, y: _add(y, one(b)(*args)), y)
    return y, (pull_first, args, plan)


def _held_experts_bwd(rows, impl, activation, res, dy):
    pull_first, args, plan = res
    one = lambda b: functools.partial(  # noqa: E731
        _held_experts_block, plan=plan, block=b, rows=rows, impl=impl, activation=activation)
    grads = jax.lax.fori_loop(
        1, _blocks_used(plan, rows),
        lambda b, grads: _add(grads, jax.vjp(one(b), *args)[1](dy)), pull_first(dy))
    return (*grads, jax.tree.map(_f0, plan))


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def dropless_block_rows(tokens, top_k, held, width):
    """Rows the held experts compute at a time: what an even routing sends
    here (``tokens * top_k * held / width`` assignments) with every held
    expert's last tile padded, in whole multiples of the tokens, so that the
    place where a further block starts stays clear of the expected load. A
    block costs what its rows in use cost, full or nearly empty (its buffers
    are sized for all of them); a load that sits AT a block's end runs a
    second block in some layers and steps and not in others."""
    expected = tokens * top_k * held // width + held * gk.TM
    return max(1, -(-expected // tokens)) * tokens


def dropless_moe_layer(params, x, *, top_k, experts_held=None,
                       normalize_weights=True, impl="auto", score="softmax",
                       route_scale=1.0, router_bias=None, shared_gate=True,
                       sequence_balance=False, activation="silu_gate", groups=1,
                       groups_kept=1):
    """Sparse SwiGLU experts without token dropping, plus a shared expert,
    over ``x`` (..., hidden).

    ``activation="relu2"``: ungated experts, ``relu(x W_up)^2 W_down`` — the
    leaves ``w_up`` (held, hidden, F) and ``shared_up`` (hidden, Fs) stand
    where ``w_gate_up`` and ``shared_gate_up`` do below.

    ``params``: ``router`` (hidden, E) at the router's FULL width;
    ``w_gate_up`` (held, hidden, 2 F) and ``w_down`` (held, F, hidden) of the
    experts held here; ``shared_gate_up`` (hidden, 2 Fs), ``shared_down``
    (Fs, hidden) and ``shared_mix`` (hidden,): the shared expert is gated by
    ``sigmoid(x . shared_mix)`` unless ``shared_gate`` is False, when it is
    added as it is and the leaf is not read. ``score``, ``route_scale`` and
    ``router_bias`` (E,), ``groups`` and ``groups_kept`` are :func:`route_topk`'s.
    ``experts_held = (first, count)``
    says which of the router's experts these are (default: all).
    ``sequence_balance``: the balance term per sequence, ``x``'s leading dims
    but the last being the sequences (:func:`route_topk`'s ``sequences``);
    default the batch-wise term. The layer
    routes every token over all E experts, computes the part of the result
    its own experts give and adds nothing for the absent ones — what one
    member of an expert-parallel group computes before the exchange (on one
    chip there is no exchange; the ragged exchange over ``ep`` is not here).

    Returns ``(y, aux)``: ``aux["load_balance_loss"]`` (over the full
    width), ``aux["expert_load"]`` (count,) int32 assignments to each expert
    held, ``aux["router_counts"]`` (E,) int32 assignments to every expert of
    the router's width (what :func:`router_bias_update` balances),
    ``aux["dropped"]`` () int32 — local assignments that no row computed,
    which this layer keeps at 0 by construction; with ``groups`` > 1 also
    ``aux["router_group_hit"]`` () float32, the share of tokens whose kept
    groups hold a group of the experts held here.
    """
    lead, H = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, H)
    T = xt.shape[0]
    E = params["router"].shape[-1]
    held = (0, E) if experts_held is None else tuple(experts_held)
    if activation not in ACTIVATIONS:
        raise ValueError(f"activation is one of {sorted(ACTIVATIONS)}, got {activation!r}")
    first, shared_first, _ = FIRST_LEAVES[activation]
    if params[first].shape[0] != held[1] or held[0] + held[1] > E:
        raise ValueError(
            f"experts_held={held} does not match the {params[first].shape[0]} "
            f"expert matrices given and a router of width {E}")
    with monitor_spans.span("moe/route"):
        top_e, top_p, aux_loss, counts, kept = route_topk(
            xt, params["router"], top_k, normalize=normalize_weights, score=score,
            bias=router_bias, scale=route_scale, impl=impl,
            sequences=max(1, T // lead[-1]) if sequence_balance and lead else None,
            groups=groups, groups_kept=groups_kept, with_kept=True)
        rows = -(-dropless_block_rows(T, top_k, held[1], E) // gk.TM) * gk.TM
        # under jax.checkpoint a policy may keep the plan by this name, so
        # that the backward pass does not make it again
        plan = jax.tree.map(
            lambda a: checkpoint_name(jax.lax.stop_gradient(a), "moe_plan"),
            dropless_plan(top_e, counts, held, rows, gk.TM))
    y = _held_experts(xt, top_p, params[first], params["w_down"], plan, rows, impl, activation)
    with monitor_spans.span("moe/shared"):
        act = ACTIVATIONS[activation](jnp.dot(xt, params[shared_first]))
        if shared_gate:
            mix = jax.nn.sigmoid(jnp.dot(xt, params["shared_mix"],
                                         preferred_element_type=jnp.float32))
            y = y + (mix[:, None] * jnp.dot(act, params["shared_down"]).astype(jnp.float32)
                     ).astype(xt.dtype)
        else:
            y = y + jnp.dot(act, params["shared_down"])
    load = jax.lax.dynamic_slice(counts, (held[0],), (held[1],))
    computed = jnp.sum(plan["row_valid"], dtype=jnp.int32)
    aux = {"load_balance_loss": aux_loss, "expert_load": load, "router_counts": counts,
           "dropped": jnp.sum(load) - computed}
    if kept is not None:       # tokens whose kept groups hold a group of the experts held
        size = E // groups
        mine = kept[:, held[0] // size:(held[0] + held[1] - 1) // size + 1]
        aux["router_group_hit"] = jnp.mean(jnp.any(mine, axis=1).astype(jnp.float32))
    return y.reshape(*lead, H), aux
