"""Tensor-model-parallel layers and collectives.

Re-design of ``apex/transformer/tensor_parallel/__init__.py``. All functions
here are written to run *inside* ``shard_map`` with the mesh's ``tp`` axis
bound — the SPMD analog of "executing on one TP rank's process".
"""

from apex_tpu.transformer.tensor_parallel.mappings import (  # noqa: F401
    copy_to_tensor_model_parallel_region,
    gather_from_tensor_model_parallel_region,
    reduce_from_tensor_model_parallel_region,
    scatter_to_tensor_model_parallel_region,
)
from apex_tpu.transformer.tensor_parallel.layers import (  # noqa: F401
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from apex_tpu.transformer.tensor_parallel.cross_entropy import (  # noqa: F401
    cross_entropy_with_grad,
    masked_mean,
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.random import (  # noqa: F401
    RngTracker,
    checkpoint,
    get_rng_tracker,
    model_parallel_rng_key,
)
from apex_tpu.transformer.tensor_parallel.utils import (  # noqa: F401
    VocabUtility,
    divide,
    split_tensor_along_last_dim,
)
from apex_tpu.transformer.tensor_parallel.data import broadcast_data  # noqa: F401
