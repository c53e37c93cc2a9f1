"""apex_tpu — a TPU-native mixed-precision & distributed-training toolkit.

A from-scratch JAX/XLA/Pallas framework with the capabilities of NVIDIA Apex
(reference: sneaxiy/apex): an automatic-mixed-precision policy engine
(``apex_tpu.amp``), data-parallel gradient synchronization and synchronized
batch-norm (``apex_tpu.parallel``), fused multi-tensor optimizers
(``apex_tpu.optimizers``), fused normalization / softmax / dense / loss ops as
Pallas TPU kernels (``apex_tpu.ops``, re-exported via ``apex_tpu.normalization``,
``apex_tpu.fused_dense``, ``apex_tpu.mlp``), Megatron-style tensor + pipeline
parallelism over a ``jax.sharding.Mesh`` (``apex_tpu.transformer``), ZeRO-style
sharded optimizers and further optional modules (``apex_tpu.contrib``), a
profiler (``apex_tpu.prof``), and runtime telemetry — metrics registry,
step-event JSONL stream, reporting CLI — with no reference analog
(``apex_tpu.monitor``, docs/OBSERVABILITY.md).

Where Apex relies on CUDA streams, NCCL process groups, and monkey-patching,
this framework uses named mesh axes + XLA collectives, functional precision
policies applied to parameter pytrees, and Pallas kernels for the hot ops.

Reference layer map: see SURVEY.md at the repo root. The top-level package
mirrors the reference's public surface (``apex/__init__.py``) without copying
its implementation.
"""

from apex_tpu.utils.logging import get_logger, set_rank_info  # noqa: F401

__version__ = "0.1.0"

# Subpackages are imported lazily by users:
#   from apex_tpu import amp, optimizers, parallel, transformer, ops, contrib
#   from apex_tpu import plan   # ParallelPlan + the CostDB-driven planner
