"""``dsv2lite-train-8k``'s step program and its plain reference's step, each
compiled at real size for a described v5e: both fit, the program holds every
kernel family the cell's readers match (every flash call the two-width form),
donates its state, and keeps in HBM no (s, s) score tensor, no 16-head copy of
the shared rotary key and no value padded to the key's width. Nothing runs; no
chip is needed. Slow (each compiles for about a minute), not tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_aot_mla.py -q -s

The configuration recomputes nothing (16.04 of 16.91 GB); ``MLA_REMAT=1``
compiles the try with every block recomputed. The bytes of every try are in
the configuration's ``aot_memory``.
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM = 15.75 * 2 ** 30   # what the compiler allows a program on one v5e chip
KERNELS = {"flash_fwd_bshd_mla", "flash_bwd_bshd_mla_fused",
           "moe_gmm", "moe_gmm_dx", "moe_gmm_dw", "xentropy_stats"}
SEQ = 8192


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


def cell_config():
    with open(os.path.join(HERE, "configs", "deepseek-v2-lite-train1.json")) as f:
        return json.load(f)


def used(m):
    return (m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
            + m.temp_size_in_bytes)


@pytest.mark.slow
def test_mla_train_step_fits_and_holds_its_kernels(topo, as_on_tpu):
    from apex_tpu.parallel import mesh as mesh_lib
    from benchmarks.adapters import train_o2_mla

    config = cell_config()
    if os.environ.get("MLA_REMAT") == "1":
        config["engine"] = dict(config["engine"], remat=True)
    ctx = {"config": config, "mix": {"params": {"seq": SEQ}}, "chips": 1, "seed": 1}
    t = train_o2_mla.Trainer(ctx, devices=list(topo.devices[:1]))
    try:
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=t.replicated)
        state = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=t.replicated),
            jax.eval_shape(t.init_state, key))
        rows = jax.ShapeDtypeStruct((t.rows, SEQ), jnp.int32, sharding=t.by_row)
        compiled = t.step.lower(*state, rows, rows).compile()
    finally:
        mesh_lib.destroy_model_parallel()
    m = compiled.memory_analysis()
    text = compiled.as_text()
    names = set(re.findall(r"%((?:moe_gmm|flash|xentropy)[a-z_]*?)\.?\d* = ", text))
    print(f"\nstate {m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB, total {used(m) / 1e9:.3f} GB; "
          f"{text.count('tpu_custom_call')} Mosaic calls: {sorted(names)}")
    assert used(m) < HBM
    assert m.alias_size_in_bytes >= 0.99 * m.output_size_in_bytes   # state donated
    assert names == KERNELS
    assert m.argument_size_in_bytes > 0.25 * 16e9      # the state alone passes the floor
    # what the kernel is held to, from the program's own buffers: no score
    # tensor, the rotary key at ONE head, values and outputs at 128
    shapes = set(re.findall(r"(?:bf16|f32)\[([\d,]+)\]", text))
    assert not any(s.endswith(f"{SEQ},{SEQ}") for s in shapes)
    assert f"2,1,{SEQ},64" in shapes                    # the shared key, head-major
    assert not {f"2,{SEQ},16,64", f"2,16,{SEQ},64"} - shapes   # q's rotary part, both layouts
    flash = [line for line in text.splitlines() if re.search(r"%flash_(fwd|bwd)", line)]
    assert flash and not any(f"[2,{SEQ},3072]" in line or f"[2,{SEQ},16,192]" in line
                             or f"[2,{SEQ},4096]" in line for line in flash)


@pytest.mark.slow
def test_mla_reference_step_fits(topo):
    """The float32 reference's own step (weights, Adam's state and gradients
    at 4 bytes each, attention rows and the wide feed-forwards in recomputed
    blocks) beside nothing else on the chip."""
    from jax.sharding import SingleDeviceSharding
    from benchmarks.reference import mla_ref

    d = mla_ref.dims(cell_config())
    one = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    w = jax.eval_shape(lambda k: mla_ref.make_weights(d, k),
                       jax.ShapeDtypeStruct((2,), jnp.uint32))
    opt = jax.eval_shape(mla_ref.adam_init, w)
    rows = jax.ShapeDtypeStruct((2, SEQ), jnp.int32, sharding=one)

    def step(w, opt, tokens, targets):
        return mla_ref.train_step(w, opt, d, tokens, targets, lr=1e-5)

    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
            place(w), place(opt), rows, rows).compile()
    m = compiled.memory_analysis()
    print(f"\nreference: arguments {m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB, total {used(m) / 1e9:.3f} GB "
          f"({used(m) / 2 ** 30:.2f} of 15.75 GiB)")
    assert used(m) < HBM
