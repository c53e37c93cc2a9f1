"""``trinity-train-8k``'s step program compiled at real size for a described
v5e: it fits with the recomputation the configuration chose, holds every
kernel family the cell's readers match (the banded flash kernels under their
own names beside the unbanded ones), and donates its state, the selection
bias with it. Nothing runs; no chip is needed. Slow (the step compiles for
over a minute), not tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_aot_afmoe.py -q -s

``AFMOE_REMAT=0`` compiles the try with nothing recomputed. The bytes of
every try are in the configuration's ``aot_memory``.
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM = 15.75 * 2 ** 30   # what the compiler allows a program on one v5e chip
# the one-pass backwards since PR 31 (``flash_bwd_bshd[_win]_dq`` / ``_dkv`` before it)
KERNELS = {"flash_fwd_bshd", "flash_bwd_bshd_fused", "flash_fwd_bshd_win", "flash_bwd_bshd_win_fused",
           "moe_gmm", "moe_gmm_dx", "moe_gmm_dw", "xentropy_stats"}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the compiler away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def as_on_tpu(monkeypatch):
    from apex_tpu.ops import _backend
    monkeypatch.setattr(_backend, "backend_platform", lambda: "tpu")


@pytest.mark.slow
def test_afmoe_train_step_fits_and_holds_its_kernels(topo, as_on_tpu):
    from apex_tpu.parallel import mesh as mesh_lib
    from benchmarks.adapters import train_o2_afmoe

    with open(os.path.join(HERE, "configs", "trinity-mini-train1.json")) as f:
        config = json.load(f)
    if os.environ.get("AFMOE_REMAT") == "0":
        config["engine"] = dict(config["engine"], remat=False)
    ctx = {"config": config, "mix": {"params": {"seq": 8192}}, "chips": 1, "seed": 1}
    t = train_o2_afmoe.Trainer(ctx, devices=list(topo.devices[:1]))
    try:
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=t.replicated)
        state = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=t.replicated),
            jax.eval_shape(t.init_state, key))
        rows = jax.ShapeDtypeStruct((t.rows, 8192), jnp.int32, sharding=t.by_row)
        compiled = t.step.lower(*state, rows, rows).compile()
    finally:
        mesh_lib.destroy_model_parallel()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
             + m.temp_size_in_bytes)
    text = compiled.as_text()
    names = set(re.findall(r"%((?:moe_gmm|flash|xentropy)[a-z_]*?)\.?\d* = ", text))
    print(f"\nstate {m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB, total {total / 1e9:.3f} GB; "
          f"{text.count('tpu_custom_call')} Mosaic calls: {sorted(names)}")
    assert total < HBM
    assert m.alias_size_in_bytes >= 0.99 * m.output_size_in_bytes   # state donated
    assert names == KERNELS
    assert m.argument_size_in_bytes > 0.25 * 16e9      # the state alone passes the floor
