"""Data-parallel gradient synchronization.

TPU-native re-design of ``apex.parallel.DistributedDataParallel``
(``apex/parallel/distributed.py:129``). The reference earns its keep by
overlapping NCCL all-reduces with backward compute: per-param autograd hooks
(``:319-408``), greedy flat-bucket construction (``:164,367-390``), side
streams (``:425-475``). On TPU none of that machinery exists at the user
level: grads of a ``pjit``-ed loss over a batch sharded on the ``dp`` axis are
reduced by XLA-inserted all-reduces, which the latency-hiding scheduler
overlaps with the backward pass automatically. What remains user-visible —
and what this module provides — are the *semantic* knobs the reference
exposes (``distributed.py:162-175``):

* ``gradient_average``            → divide by dp size (pmean vs psum)
* ``gradient_predivide_factor``   → pre-divide locally, post-divide the rest
  (numerics for very large dp counts)
* ``allreduce_always_fp32``       → upcast grads before the reduction

plus sharding helpers that put the batch on the ``dp`` axis in the first
place. The ``Reducer`` manual-call variant (``distributed.py:89``) is
:func:`all_reduce_gradients` used directly inside ``shard_map``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu.monitor import spans as monitor_spans
from apex_tpu.parallel import mesh as mesh_lib

PyTree = Any


@dataclasses.dataclass(frozen=True)
class DistributedGradients:
    """Config mirroring apex DDP's reduction options
    (``apex/parallel/distributed.py:162-175``)."""

    axis_name: str = mesh_lib.DATA_AXIS
    gradient_average: bool = True
    gradient_predivide_factor: float = 1.0
    allreduce_always_fp32: bool = False

    def __call__(self, grads: PyTree) -> PyTree:
        return all_reduce_gradients(
            grads,
            axis_name=self.axis_name,
            gradient_average=self.gradient_average,
            gradient_predivide_factor=self.gradient_predivide_factor,
            allreduce_always_fp32=self.allreduce_always_fp32,
        )


def all_reduce_gradients(
    grads: PyTree,
    *,
    axis_name: str = mesh_lib.DATA_AXIS,
    gradient_average: bool = True,
    gradient_predivide_factor: float = 1.0,
    allreduce_always_fp32: bool = False,
) -> PyTree:
    """All-reduce a grad pytree across ``axis_name`` inside ``shard_map``.

    Matches the arithmetic of ``allreduce_bucket``
    (``apex/parallel/distributed.py:425-475``): optional fp32 upcast, divide
    by ``predivide_factor`` before the reduce and by
    ``world_size/predivide_factor`` after (so the full division happens in two
    stages), or plain average / sum.
    """
    from apex_tpu.monitor import hooks as monitor_hooks

    if monitor_hooks.enabled():  # trace-time count, zero run-time cost
        monitor_hooks.count_collective(
            "psum", bytes=monitor_hooks.tree_bytes(grads), axis=axis_name)

    def reduce_one(g: jax.Array) -> jax.Array:
        orig_dtype = g.dtype
        if allreduce_always_fp32:
            g = g.astype(jnp.float32)
        if gradient_predivide_factor != 1.0:
            g = g / gradient_predivide_factor
        g = jax.lax.psum(g, axis_name)
        if gradient_average:
            world = jax.lax.axis_size(axis_name)
            if gradient_predivide_factor != 1.0:
                g = g * (gradient_predivide_factor / world)
            else:
                g = g / world
        if allreduce_always_fp32:
            g = g.astype(orig_dtype)
        return g

    with monitor_spans.span("ddp/allreduce"):
        return jax.tree.map(reduce_one, grads)


# Alias with the reference's conceptual name.
cross_replica_gradients = all_reduce_gradients


def data_parallel_sharding(
    mesh: Optional[Mesh] = None, *, batch_axis: int = 0
) -> NamedSharding:
    """Sharding that splits the batch dimension over the ``dp`` axis — the
    declaration that replaces wrapping a model in DDP."""
    mesh = mesh or mesh_lib.get_mesh()
    spec = [None] * (batch_axis + 1)
    spec[batch_axis] = mesh_lib.DATA_AXIS
    return NamedSharding(mesh, P(*spec))


def replicate(tree: PyTree, mesh: Optional[Mesh] = None) -> PyTree:
    """Replicate a pytree across the whole mesh — the init-time param
    broadcast (``apex/parallel/distributed.py:253``), done once, by XLA."""
    mesh = mesh or mesh_lib.get_mesh()
    sharding = NamedSharding(mesh, P())
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)
