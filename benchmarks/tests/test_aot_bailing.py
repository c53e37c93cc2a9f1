"""``ling3-train-8k``'s step program and its plain reference's step, each
compiled at real size for a described v5e: both fit 0.5 GB under what the
compiler allows, the program holds every kernel family the cell's readers
match (``kda_fwd`` / ``kda_bwd`` among them, every flash call the two-width
form), donates its state, and keeps in HBM no (s, s) score tensor. Nothing
runs; no chip is needed. Slow (each compiles for a minute or two), not tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_aot_bailing.py -q -s

``BAILING_LAYERS=7`` compiles the deeper try (published layers 1-7),
``BAILING_ROWS=1`` one row a chip, ``BAILING_REMAT=0`` the try that recomputes
nothing. The bytes of every try are in the configuration's ``aot_memory``.
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM = 15.75 * 2 ** 30   # what the compiler allows a program on one v5e chip
ROOM = 0.5e9            # what a try has to leave under it
KERNELS = {"kda_fwd", "kda_bwd", "conv_silu_fwd", "conv_silu_bwd", "flash_fwd_bshd_mla",
           "flash_bwd_bshd_mla_fused", "moe_gmm", "moe_gmm_dx", "moe_gmm_dw", "moe_top_rounds",
           "moe_rows_gather", "moe_rows_gather_dots", "moe_rows_pack", "moe_rows_combine",
           "moe_rows_combine_weighted", "xentropy_stats"}
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
    with open(os.path.join(HERE, "configs", "ling-3.0-flash-train1.json")) as f:
        config = json.load(f)
    layers = int(os.environ.get("BAILING_LAYERS", config["num_hidden_layers"]))
    config.update(num_hidden_layers=layers, layers_kept=list(range(1, layers + 1)))
    config["engine"] = dict(config["engine"],
                            rows_per_chip=int(os.environ.get("BAILING_ROWS", 2)),
                            remat=os.environ.get("BAILING_REMAT", "1") == "1")
    return config


def used(m):
    return (m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
            + m.temp_size_in_bytes)


@pytest.mark.slow
def test_bailing_train_step_fits_and_holds_its_kernels(topo, as_on_tpu):
    from apex_tpu.parallel import mesh as mesh_lib
    from benchmarks.adapters import train_o2_bailing

    config = cell_config()
    ctx = {"config": config, "mix": {"params": {"seq": SEQ}}, "chips": 1, "seed": 1}
    t = train_o2_bailing.Trainer(ctx, devices=list(topo.devices[:1]))
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
    names = set(re.findall(r"%((?:kda|conv_silu|moe|flash|xentropy)[a-z_]*?)\.?\d* = ", text))
    print(f"\n{config['num_hidden_layers']} layers, {t.rows} rows, remat {config['engine']['remat']}: "
          f"state {m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB, total {used(m) / 1e9:.3f} GB; "
          f"{text.count('tpu_custom_call')} Mosaic calls: {sorted(names)}")
    assert used(m) < HBM - ROOM
    assert m.alias_size_in_bytes >= 0.99 * m.output_size_in_bytes   # state donated
    assert names == KERNELS
    assert m.argument_size_in_bytes > 0.25 * 16e9      # the state alone passes the floor
    shapes = set(re.findall(r"(?:bf16|f32)\[([\d,]+)\]", text))
    assert not any(s.endswith(f"{SEQ},{SEQ}") for s in shapes)
    # the forward kernels once a layer: what ``remat`` keeps spares their second run
    count = lambda name: len(re.findall(rf"%{name}\.?\d* = ", text))  # noqa: E731
    kda = config["num_hidden_layers"] - 1
    assert (count("kda_fwd"), count("kda_bwd")) == (kda, kda)
    assert (count("flash_fwd_bshd_mla"), count("flash_bwd_bshd_mla_fused")) == (1, 1)


@pytest.mark.slow
def test_bailing_reference_step_fits(topo):
    """The float32 reference's own step as the adapter runs it, beside nothing
    else on the chip: its two halves are two programs — the gradient (weights
    and gradients at 4 bytes each; a row at a time inside every mixer, the
    scanned states and the wide feed-forwards in recomputed blocks) and
    Adam's update (weights, both moments and the gradients) — with the moments
    on the host in between."""
    from jax.sharding import SingleDeviceSharding
    from benchmarks.reference import bailing_ref

    config = cell_config()
    d = bailing_ref.dims(config)
    one = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    w = jax.eval_shape(lambda k: bailing_ref.make_weights(d, k),
                       jax.ShapeDtypeStruct((2,), jnp.uint32))
    opt = jax.eval_shape(bailing_ref.adam_init, w)
    bias = jax.eval_shape(lambda: bailing_ref.bias_init(d))
    rows = jax.ShapeDtypeStruct((config["engine"]["rows_per_chip"], SEQ), jnp.int32, sharding=one)
    with jax.default_matmul_precision("highest"):
        halves = {
            "gradient": jax.jit(lambda w, b, x, y: bailing_ref.grad_step(w, b, d, x, y)).lower(
                place(w), place(bias), rows, rows).compile(),
            "update": jax.jit(lambda w, o, g: bailing_ref.adam_update(w, o, g, lr=1e-5),
                              donate_argnums=(0, 1)).lower(
                place(w), place(opt), place(w)).compile()}
    for name, compiled in halves.items():
        m = compiled.memory_analysis()
        print(f"\nreference's {name}, {config['num_hidden_layers']} layers: arguments "
              f"{m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} GB, total {used(m) / 1e9:.3f} GB "
              f"({used(m) / 2 ** 30:.2f} of 15.75 GiB)")
        assert used(m) < HBM - ROOM
