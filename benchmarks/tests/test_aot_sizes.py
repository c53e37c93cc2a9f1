"""Every cell's step program compiled at real size for a described v5e, with
the bytes ``memory_analysis()`` reports: the evidence for each ``reduced``
and batch size in the configuration files. Nothing runs; no chip is
needed. Slow (each step program compiles for half a minute to a minute and
a half), so these are not part of the tier-1 run:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_aot_sizes.py -q -s
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM = 15.75 * 2 ** 30   # what the compiler allows a program on one v5e chip: GiB (16.91 GB)


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
    """The program picks its kernels by ``jax.default_backend()``; steer it
    here, in the test, to what it picks on the chip."""
    from apex_tpu.ops import _backend
    monkeypatch.setattr(_backend, "backend_platform", lambda: "tpu")


def config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def total_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes), m


@pytest.mark.parametrize("name,seq,chips,kernels", [
    ("starcoderbase-1b-train1", 8192, 1, 17),    # 2 flash kernels a layer + CE
    ("gpt2-medium", 1024, 4, 49)])
def test_train_step_fits(topo, as_on_tpu, name, seq, chips, kernels):
    from apex_tpu.parallel import mesh as mesh_lib
    from benchmarks.adapters import train_o2_dp

    ctx = {"config": config(name), "mix": {"params": {"seq": seq}}, "chips": chips,
           "seed": 1}
    t = train_o2_dp.Trainer(ctx, devices=list(topo.devices[:chips]))
    try:
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=t.replicated)
        state = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=t.replicated),
            jax.eval_shape(t.init_state, key))
        rows = jax.ShapeDtypeStruct((t.rows, seq), jnp.int32, sharding=t.by_row)
        compiled = t.step.lower(*state, rows, rows).compile()
    finally:
        mesh_lib.destroy_model_parallel()
    total, m = total_bytes(compiled)
    text = compiled.as_text()
    print(f"\n{name}: state {m.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.2f} GB, total {total / 1e9:.2f} GB per chip; "
          f"{text.count('tpu_custom_call')} Mosaic calls, "
          f"{text.count('all-reduce')} mentions of all-reduce")
    assert total < HBM
    assert m.alias_size_in_bytes >= 0.99 * m.output_size_in_bytes   # state donated
    assert text.count("tpu_custom_call") == kernels
    assert (text.count("all-reduce") > 0) == (chips > 1)
