"""fp32 master weights (opt-level O2).

Re-design of ``apex/amp/_process_optimizer.py``'s master-weight machinery:
the reference clones fp16 params into fp32 masters and swaps them into the
optimizer's ``param_groups`` (``_process_optimizer.py:28-90``), then patches
``step`` to copy master→model afterwards (``:354-364``).

Functionally: the fp32 master pytree is the single source of truth; the model
(compute-dtype) params are a *derived* cast, re-materialized once per step.
The master→model copy (``amp_C.multi_tensor_scale`` in the reference,
``_process_optimizer.py:14-25``) is one fused ``astype`` XLA folds into the
next forward pass.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.amp.policy import Policy
from apex_tpu.amp.scaler import apply_if_finite
from apex_tpu.monitor import spans as monitor_spans
from apex_tpu.utils.pytree import tree_cast

PyTree = Any


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MasterWeights:
    """fp32 masters + the derived compute-dtype model params."""

    master: PyTree                 # fp32, what the optimizer updates
    model: PyTree                  # param_dtype (bf16/fp16), what forward uses
    param_dtype: Any = dataclasses.field(metadata=dict(static=True), default=jnp.bfloat16)
    # leaf names the model copy keeps in fp32 (the reference's
    # ``keep_batchnorm_fp32`` by module type, here by name): parameters that
    # enter an exponent, such as a recurrent layer's log decay
    keep_float32: Tuple[str, ...] = dataclasses.field(metadata=dict(static=True), default=())

    @classmethod
    def create(cls, params: PyTree, policy: Policy,
               keep_float32: Tuple[str, ...] = ()) -> "MasterWeights":
        """Initialize masters from (possibly half) params — the reference's
        ``lazy_init_with_master_weights`` (``_process_optimizer.py:28-90``).
        Leaves whose path holds one of ``keep_float32`` stay fp32 in the
        model copy too."""
        master = tree_cast(params, jnp.float32)
        return cls(master=master, model=None, param_dtype=policy.param_dtype,
                   keep_float32=tuple(keep_float32)).resync()

    def resync(self) -> "MasterWeights":
        """Re-derive model params from masters (master→model copy,
        ``_process_optimizer.py:354-364``)."""
        if not self.keep_float32:
            return dataclasses.replace(self, model=tree_cast(self.master, self.param_dtype))
        keep = lambda path: any(n in jax.tree_util.keystr(path) for n in self.keep_float32)  # noqa: E731
        model = jax.tree_util.tree_map_with_path(
            lambda path, a: a if keep(path) else tree_cast(a, self.param_dtype), self.master)
        return dataclasses.replace(self, model=model)


def apply_updates_with_master(
    weights: MasterWeights,
    updates: PyTree,
    *,
    grads_finite: Optional[jax.Array] = None,
) -> MasterWeights:
    """Apply optax-style additive ``updates`` to the fp32 masters, skip when
    grads overflowed, and re-derive the model params. The full O2 step
    epilogue as one pure function."""
    with monitor_spans.span("amp/apply_master"):
        new_master = jax.tree.map(lambda p, u: p + jnp.asarray(u, p.dtype), weights.master, updates)
        if grads_finite is not None:
            new_master = apply_if_finite(weights.master, new_master, grads_finite)
        return dataclasses.replace(weights, master=new_master).resync()


def o2_state_dict_params(weights: MasterWeights) -> PyTree:
    """fp32 params for checkpointing regardless of cast — the reference's
    ``O2StateDictHook`` (``apex/amp/_initialize.py:133-143,207-210``)."""
    return weights.master
