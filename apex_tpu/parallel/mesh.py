"""Global device-mesh bookkeeping for N-D parallelism.

TPU-native replacement for the reference's ``apex/transformer/parallel_state.py``:
where the reference builds a zoo of NCCL process groups for DP x TP x PP (+
virtual PP + embedding groups, ``parallel_state.py:73-247``) and exposes ~40
rank/world-size accessors (``:262-549``), a JAX SPMD program needs exactly one
``jax.sharding.Mesh`` with named axes; collectives reference axes by name and
XLA lowers them to ICI/DCN ring/tree ops.

Axis layout (outer → inner): ``('dp', 'pp', 'cp', 'tp')``. ``tp`` is
innermost so tensor-parallel collectives ride the fastest ICI links; ``dp``
outermost so data-parallel all-reduces tolerate DCN between slices. Context
parallelism (``cp``, for ring attention / long context) and expert parallelism
(``ep``, folded over ``dp``) are first-class here even though the reference
lacks them (SURVEY.md §2.3).

The "rank" accessors come in two flavors:
  * world sizes — module level, from the mesh shape (host-side);
  * ranks — only meaningful per-device, i.e. *inside* ``shard_map``; use
    ``jax.lax.axis_index(axis)``. Host-side code that needs "my rank" the way
    the reference does (e.g. ``get_tensor_model_parallel_rank()``,
    ``parallel_state.py:324``) should restructure to be rank-free — SPMD
    programs are written once for all ranks.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from apex_tpu.utils.logging import get_logger, set_rank_info

logger = get_logger(__name__)

# Canonical axis names. The reference's group getters (e.g.
# get_tensor_model_parallel_group, parallel_state.py:262+) map to these names.
DATA_AXIS = "dp"
PIPELINE_AXIS = "pp"
CONTEXT_AXIS = "cp"
TENSOR_AXIS = "tp"
EXPERT_AXIS = "ep"  # a dedicated sub-axis split out of dp when
# expert_parallel_size > 1 (the mesh becomes 5-D: dp, ep, pp, cp, tp with
# ep just inside dp so expert all_to_alls ride closer links); data-parallel
# collectives then span BOTH axes — use data_parallel_axis_names()

_MESH: Optional[Mesh] = None
_SPEC: Optional["MeshSpec"] = None


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Static description of the parallel decomposition.

    Mirrors the arguments of the reference's ``initialize_model_parallel``
    (``apex/transformer/parallel_state.py:73-110``) plus the TPU-first
    extensions (context/expert parallelism).
    """

    data_parallel_size: int = 1
    tensor_model_parallel_size: int = 1
    pipeline_model_parallel_size: int = 1
    context_parallel_size: int = 1
    expert_parallel_size: int = 1
    virtual_pipeline_model_parallel_size: Optional[int] = None
    # Encoder-decoder (T5-class) two-segment pipelines: stages
    # [0, split) run the encoder, [split, pp) the decoder (reference
    # ``parallel_state.py:147-149``; consumed by
    # ``pipeline_parallel.encoder_decoder``).
    pipeline_model_parallel_split_rank: Optional[int] = None

    def __post_init__(self):
        # divisibility/axis legality is ParallelPlan.validate()'s job —
        # ONE validator, one message style, whichever door (GPTConfig,
        # make_mesh, build_schedule) an illegal combo walks through
        from apex_tpu.plan.parallel_plan import ParallelPlan, PlanError

        v = self.virtual_pipeline_model_parallel_size
        if v is not None and self.pipeline_model_parallel_size < 2:
            # stricter than the plan's lenient v=1: ASKING for virtual
            # pipelining without a pipeline is a config error here
            raise ValueError(
                f"virtual_pipeline_model_parallel_size={v}: virtual "
                "pipeline parallelism requires "
                "pipeline_model_parallel_size >= 2")
        try:
            ParallelPlan(
                dp=self.data_parallel_size,
                tp=self.tensor_model_parallel_size,
                pp=self.pipeline_model_parallel_size,
                cp=self.context_parallel_size,
                ep=self.expert_parallel_size,
                virtual_chunks=v if v is not None else 1)
        except PlanError as e:
            raise ValueError(str(e)) from None
        split = self.pipeline_model_parallel_split_rank
        if split is not None and not (
                0 < split < self.pipeline_model_parallel_size):
            raise ValueError(
                f"pipeline_model_parallel_split_rank ({split}) must lie "
                f"strictly inside [1, pp) — both segments need at least one "
                f"stage (pp={self.pipeline_model_parallel_size})")

    @property
    def model_parallel_size(self) -> int:
        return (
            self.tensor_model_parallel_size
            * self.pipeline_model_parallel_size
            * self.context_parallel_size
        )

    @property
    def world_size(self) -> int:
        return self.data_parallel_size * self.model_parallel_size


def _apply_plan(plan: "ParallelPlan", devices, loose):
    """Unpack a ParallelPlan into the loose axis sizes + the sliced
    device list (dp is authoritative — a host exposing more devices
    must not silently widen it). One helper for both mesh doors so a
    new plan field cannot be threaded through one and not the other.
    ``loose`` carries the door's positional (tp, pp, cp, ep) kwargs: a
    non-default loose size that disagrees with the plan is an eager
    error (the GPTConfig rule) — never a silent merge."""
    for name, got, want in (
            ("tensor_model_parallel_size", loose[0], plan.tp),
            ("pipeline_model_parallel_size", loose[1], plan.pp),
            ("context_parallel_size", loose[2], plan.cp),
            ("expert_parallel_size", loose[3], plan.ep)):
        if got != 1 and got != want:
            raise ValueError(
                f"{name}={got} contradicts plan={plan.describe()} "
                f"(which implies {name}={want}); pass the knob through "
                f"the plan, not alongside it")
    if plan.world_size > len(devices):
        raise RuntimeError(
            f"plan {plan.describe()} spans {plan.world_size} "
            f"device(s); only {len(devices)} available")
    return (plan.tp, plan.pp, plan.cp, plan.ep,
            devices[: plan.world_size])


def initialize_model_parallel(
    tensor_model_parallel_size: int = 1,
    pipeline_model_parallel_size: int = 1,
    *,
    context_parallel_size: int = 1,
    virtual_pipeline_model_parallel_size: Optional[int] = None,
    expert_parallel_size: int = 1,
    pipeline_model_parallel_split_rank: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    plan: Optional["ParallelPlan"] = None,
) -> Mesh:
    """Build and install the global mesh.

    Equivalent of ``parallel_state.initialize_model_parallel``
    (``apex/transformer/parallel_state.py:73-247``): validates divisibility,
    computes the data-parallel size from the device count, and constructs the
    decomposition — but as ONE mesh rather than O(world_size) process groups.
    The reference's rank-ordering convention (tp fastest-varying, then pp,
    then dp) is preserved so the same global batch maps to the same devices.
    """
    global _MESH, _SPEC
    if devices is None:
        devices = jax.devices()
    if plan is not None:
        (tensor_model_parallel_size, pipeline_model_parallel_size,
         context_parallel_size, expert_parallel_size,
         devices) = _apply_plan(plan, devices, (
             tensor_model_parallel_size, pipeline_model_parallel_size,
             context_parallel_size, expert_parallel_size))
        v = virtual_pipeline_model_parallel_size
        if v is not None and v != plan.virtual_chunks:
            # the plan is the single source of truth: a loose v that
            # disagrees must not silently merge into the MeshSpec
            raise ValueError(
                f"virtual_pipeline_model_parallel_size={v} contradicts "
                f"plan={plan.describe()} (virtual_chunks="
                f"{plan.virtual_chunks}); pass the knob through the "
                f"plan, not alongside it")
        virtual_pipeline_model_parallel_size = (
            plan.virtual_chunks if plan.virtual_chunks > 1 else None)
    world_size = len(devices)
    model_parallel = (
        tensor_model_parallel_size * pipeline_model_parallel_size * context_parallel_size
    )
    if world_size % model_parallel != 0:
        raise RuntimeError(
            f"world size ({world_size}) is not divisible by "
            f"tp ({tensor_model_parallel_size}) x pp ({pipeline_model_parallel_size}) "
            f"x cp ({context_parallel_size})"
        )
    data_parallel_size = world_size // model_parallel
    spec = MeshSpec(
        data_parallel_size=data_parallel_size,
        tensor_model_parallel_size=tensor_model_parallel_size,
        pipeline_model_parallel_size=pipeline_model_parallel_size,
        context_parallel_size=context_parallel_size,
        expert_parallel_size=expert_parallel_size,
        virtual_pipeline_model_parallel_size=virtual_pipeline_model_parallel_size,
        pipeline_model_parallel_split_rank=pipeline_model_parallel_split_rank,
    )
    mesh = _build_mesh(
        devices, data_parallel_size, expert_parallel_size,
        pipeline_model_parallel_size, context_parallel_size,
        tensor_model_parallel_size,
    )
    _MESH, _SPEC = mesh, spec
    set_rank_info(get_rank_info())
    logger.info("initialized model parallel: %s", spec)
    return mesh


def make_mesh(
    tensor_model_parallel_size: int = 1,
    pipeline_model_parallel_size: int = 1,
    context_parallel_size: int = 1,
    expert_parallel_size: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
    *,
    plan: Optional["ParallelPlan"] = None,
) -> Mesh:
    """Build a mesh without installing it globally (for tests / local use).

    ``plan`` is the preferred spelling (ISSUE 12): axis sizes come from
    one validated :class:`~apex_tpu.plan.parallel_plan.ParallelPlan`,
    and the device list is sliced to exactly ``plan.world_size`` (the
    plan's dp is authoritative — a host exposing more devices must not
    silently widen dp). The positional sizes stay as the deprecated
    loose-kwarg shim."""
    if devices is None:
        devices = jax.devices()
    if plan is not None:
        (tensor_model_parallel_size, pipeline_model_parallel_size,
         context_parallel_size, expert_parallel_size,
         devices) = _apply_plan(plan, devices, (
             tensor_model_parallel_size, pipeline_model_parallel_size,
             context_parallel_size, expert_parallel_size))
    model_parallel = (
        tensor_model_parallel_size * pipeline_model_parallel_size * context_parallel_size
    )
    dp = len(devices) // model_parallel
    if dp == 0:
        raise RuntimeError(
            f"{len(devices)} device(s) cannot host tp ({tensor_model_parallel_size}) "
            f"x pp ({pipeline_model_parallel_size}) x cp ({context_parallel_size})"
        )
    return _build_mesh(
        devices[: dp * model_parallel], dp, expert_parallel_size,
        pipeline_model_parallel_size, context_parallel_size,
        tensor_model_parallel_size,
    )


def _build_mesh(devices, dp, ep, pp, cp, tp) -> Mesh:
    """The one place the device array is laid out. With ``ep > 1`` a
    dedicated expert axis splits out of dp (ep INSIDE dp: expert
    all_to_alls stay within each dp group's closer links) and the mesh is
    5-D; otherwise the classic 4-D layout."""
    if ep > 1:
        if dp % ep:
            # same validator (and message style) as every other door
            from apex_tpu.plan.parallel_plan import ParallelPlan, PlanError
            try:
                ParallelPlan(dp=dp, ep=ep)
            except PlanError as e:
                raise ValueError(str(e)) from None
            raise ValueError(  # pragma: no cover - plan rejects first
                f"expert_parallel_size ({ep}) must divide the "
                f"data-parallel extent ({dp})")
        device_array = np.asarray(devices).reshape(dp // ep, ep, pp, cp, tp)
        return Mesh(device_array, (DATA_AXIS, EXPERT_AXIS, PIPELINE_AXIS,
                                   CONTEXT_AXIS, TENSOR_AXIS))
    device_array = np.asarray(devices).reshape(dp, pp, cp, tp)
    return Mesh(device_array,
                (DATA_AXIS, PIPELINE_AXIS, CONTEXT_AXIS, TENSOR_AXIS))


def hybrid_device_order(devices: Sequence, model_parallel: int) -> list:
    """Reorder ``devices`` so the model-parallel axes (the mesh's inner
    ``model_parallel`` extent) stay INSIDE one slice's ICI and the
    data-parallel axis (outermost) spans slices over DCN.

    Multi-slice TPU pods expose ``device.slice_index``; within a slice,
    ``device.id`` preserves the ICI torus order jax already provides. The
    flat reshape in :func:`_build_mesh` then puts slice boundaries exactly
    at dp-group boundaries — dp all-reduces ride DCN, tp/cp/pp/ep
    collectives never leave a slice (the scaling-book hybrid recipe;
    jax's ``mesh_utils.create_hybrid_device_mesh`` does the same
    arrangement for the 2-level case).

    Pure list-ordering (no Mesh construction) so it is testable with stub
    devices. Raises if any slice's device count is not a multiple of
    ``model_parallel`` — a model group straddling DCN is the exact layout
    this function exists to prevent."""
    slices: dict = {}
    for d in devices:
        slices.setdefault(getattr(d, "slice_index", 0), []).append(d)
    if len(slices) == 1:
        return list(devices)  # single slice (or CPU): nothing to arrange
    for idx, devs in slices.items():
        if len(devs) % model_parallel:
            raise RuntimeError(
                f"slice {idx} holds {len(devs)} devices — not a multiple of "
                f"the model-parallel extent ({model_parallel}); a tp/pp/cp "
                f"group would straddle DCN")
    out = []
    for idx in sorted(slices):
        out.extend(sorted(slices[idx], key=lambda d: d.id))
    return out


def make_hybrid_mesh(
    tensor_model_parallel_size: int = 1,
    pipeline_model_parallel_size: int = 1,
    context_parallel_size: int = 1,
    expert_parallel_size: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """:func:`make_mesh` with the multi-slice (DCN) device arrangement of
    :func:`hybrid_device_order` applied first. On a single slice (or CPU)
    this is exactly ``make_mesh``."""
    if devices is None:
        devices = jax.devices()
    # the contiguous inner block of _build_mesh's reshape: ep sits just
    # INSIDE dp in the 5-D layout, so ep all_to_alls are slice-local only
    # if ep is part of the extent the slice-divisibility guard covers
    inner = (expert_parallel_size * pipeline_model_parallel_size
             * context_parallel_size * tensor_model_parallel_size)
    return make_mesh(
        tensor_model_parallel_size, pipeline_model_parallel_size,
        context_parallel_size, expert_parallel_size,
        devices=hybrid_device_order(devices, inner))


def destroy_model_parallel() -> None:
    """Tear down global state (cf. ``parallel_state.py:555-580``)."""
    global _MESH, _SPEC
    _MESH, _SPEC = None, None
    set_rank_info("")


def model_parallel_is_initialized() -> bool:
    return _MESH is not None


def get_mesh() -> Mesh:
    if _MESH is None:
        raise RuntimeError(
            "model parallel mesh is not initialized; call "
            "apex_tpu.parallel.initialize_model_parallel(...) first"
        )
    return _MESH


def get_mesh_spec() -> MeshSpec:
    if _SPEC is None:
        raise RuntimeError("model parallel mesh is not initialized")
    return _SPEC


# --- world-size accessors (host-side; cf. parallel_state.py:262-549) ---------

def get_data_parallel_world_size() -> int:
    return get_mesh_spec().data_parallel_size


def get_tensor_model_parallel_world_size() -> int:
    return get_mesh_spec().tensor_model_parallel_size


def get_pipeline_model_parallel_world_size() -> int:
    return get_mesh_spec().pipeline_model_parallel_size


def get_context_parallel_world_size() -> int:
    return get_mesh_spec().context_parallel_size


def get_expert_parallel_world_size() -> int:
    return get_mesh_spec().expert_parallel_size


def get_virtual_pipeline_model_parallel_world_size() -> Optional[int]:
    return get_mesh_spec().virtual_pipeline_model_parallel_size


def get_pipeline_model_parallel_split_rank() -> Optional[int]:
    """First decoder stage of a two-segment (encoder-decoder) pipeline, or
    None for single-segment models (``parallel_state.py:147-149``)."""
    return get_mesh_spec().pipeline_model_parallel_split_rank


def data_parallel_axis_names() -> tuple:
    """The mesh axes data parallelism spans: ``('dp',)`` normally,
    ``('dp', 'ep')`` when a dedicated expert axis is split out — pass to
    ``pmean``/``PartitionSpec`` so dp collectives and batch sharding cover
    the full data-parallel extent."""
    if get_mesh_spec().expert_parallel_size > 1:
        return (DATA_AXIS, EXPERT_AXIS)
    return (DATA_AXIS,)


def get_rank_info() -> str:
    """Short mesh descriptor for log records (cf. ``parallel_state.py:250-259``)."""
    if _SPEC is None:
        return "uninitialized"
    s = _SPEC
    return (
        f"dp{s.data_parallel_size}/pp{s.pipeline_model_parallel_size}"
        f"/cp{s.context_parallel_size}/tp{s.tensor_model_parallel_size}"
    )


# --- in-shard_map rank helpers ----------------------------------------------

def shard_map(f, mesh=None, *, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` bound to the global mesh, with the
    varying-manual-axes check off by default: Megatron-style TP code is full
    of rank-dependent slices whose replication (post all-gather) the static
    checker cannot prove — the same reason the reference asserts its own
    invariants at runtime instead (e.g. ``distributed.py:340-348``).

    The global mesh is resolved at *call* time so wrappers may be built
    before ``initialize_model_parallel()`` and survive re-initialization."""
    if mesh is not None:
        return jax.shard_map(
            f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=check_vma)

    def call(*args, **kwargs):
        return jax.shard_map(
            f, mesh=get_mesh(), in_specs=in_specs, out_specs=out_specs,
            check_vma=check_vma)(*args, **kwargs)

    return call


def axis_rank(axis: str) -> jax.Array:
    """Per-device rank along ``axis``; valid only inside shard_map/pjit with
    that axis bound (replaces get_*_rank, ``parallel_state.py:324+``)."""
    return jax.lax.axis_index(axis)


def is_pipeline_first_stage() -> jax.Array:
    return jax.lax.axis_index(PIPELINE_AXIS) == 0


def is_pipeline_last_stage() -> jax.Array:
    return jax.lax.axis_index(PIPELINE_AXIS) == jax.lax.axis_size(PIPELINE_AXIS) - 1


def is_pipeline_stage_before_split(rank=None) -> jax.Array:
    """This stage runs encoder blocks (reference ``parallel_state.py:338``).
    In-shard_map by default; pass an explicit ``rank`` for host-side use."""
    split = get_pipeline_model_parallel_split_rank()
    if rank is None:
        rank = jax.lax.axis_index(PIPELINE_AXIS)
    if split is None:
        return rank >= 0  # vacuously true, traced- and host-friendly
    return rank < split


def is_pipeline_stage_after_split(rank=None) -> jax.Array:
    """This stage runs decoder blocks (``parallel_state.py:355``)."""
    split = get_pipeline_model_parallel_split_rank()
    if rank is None:
        rank = jax.lax.axis_index(PIPELINE_AXIS)
    if split is None:
        return rank >= 0  # vacuously true
    return rank >= split


def is_pipeline_stage_at_split(rank=None) -> jax.Array:
    """Last encoder stage — the stage whose successor starts the decoder
    (``parallel_state.py:369-375``)."""
    split = get_pipeline_model_parallel_split_rank()
    if rank is None:
        rank = jax.lax.axis_index(PIPELINE_AXIS)
    if split is None:
        return rank < 0  # vacuously false
    return rank == split - 1
