"""``nemotron3-train-8k``'s step program and its plain reference's step, each
compiled at real size for a described v5e: both fit, the program holds every
kernel family the cell's readers match (the scan pair among them), donates its
state, and keeps in HBM no state a token (T, heads, N, P) and no expert
operand padded from 1,856 to 1,920 or 2,048. Nothing runs; no chip is needed.
Slow (each compiles for about a minute), not tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_aot_ssm.py -q -s

The configuration recomputes nothing (14.80 of 16.91 GB); ``SSM_REMAT=1``
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
KERNELS = {"ssd_fwd", "ssd_bwd", "conv_silu_fwd", "conv_silu_bwd", "gated_norm_fwd",
           "gated_norm_bwd", "flash_fwd_bshd", "flash_bwd_bshd_fused",
           "moe_gmm", "moe_gmm_dx", "moe_gmm_dw", "moe_rows_gather", "moe_rows_gather_dots",
           "moe_rows_pack", "moe_rows_combine", "moe_rows_combine_weighted", "xentropy_stats"}
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
    with open(os.path.join(HERE, "configs", "nemotron-3-nano-30b-a3b-train1.json")) as f:
        return json.load(f)


def used(m):
    return (m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
            + m.temp_size_in_bytes)


@pytest.mark.slow
def test_ssm_train_step_fits_and_holds_its_kernels(topo, as_on_tpu):
    from apex_tpu.parallel import mesh as mesh_lib
    from benchmarks.adapters import train_o2_ssm

    config = cell_config()
    if os.environ.get("SSM_REMAT") == "1":
        config["engine"] = dict(config["engine"], remat=True)
    ctx = {"config": config, "mix": {"params": {"seq": SEQ}}, "chips": 1, "seed": 1}
    t = train_o2_ssm.Trainer(ctx, devices=list(topo.devices[:1]))
    try:
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=t.replicated)
        state = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=t.replicated),
            jax.eval_shape(t.seeded, key, t.model.init_router_bias()))
        rows = jax.ShapeDtypeStruct((t.rows, SEQ), jnp.int32, sharding=t.by_row)
        compiled = t.step.lower(*state, rows, rows).compile()
    finally:
        mesh_lib.destroy_model_parallel()
    m = compiled.memory_analysis()
    text = compiled.as_text()
    names = set(re.findall(
        r"%((?:ssd|conv_silu|gated_norm|moe_gmm|moe_rows|flash|xentropy)[a-z_]*?)\.?\d* = ", text))
    print(f"\nstate {m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB, total {used(m) / 1e9:.3f} GB; "
          f"{text.count('tpu_custom_call')} Mosaic calls: {sorted(names)}")
    assert used(m) < HBM
    assert m.alias_size_in_bytes >= 0.99 * m.output_size_in_bytes   # state donated
    assert names == KERNELS
    assert m.argument_size_in_bytes > 0.25 * 16e9      # the state alone passes the floor
    # the scan once a layer and pass: three state-space layers
    calls = lambda name: len(re.findall(rf"%{name}\.?\d* = ", text))  # noqa: E731
    assert calls("ssd_fwd") == calls("ssd_bwd") == 3
    # what the kernels are held to, from the program's own buffers: no state a
    # token, no expert operand at a padded width
    shapes = set(re.findall(r"(?:bf16|f32)\[([\d,]+)\]", text))
    assert not any(s.endswith(",128,64") and f"{SEQ}," in s for s in shapes)
    # (2,048 alone is half of a row widened to 4,096 for the row DMA: the movements', no expert's)
    wide = lambda s: re.search(r"(^|,)1920(,|$)", s) or (  # noqa: E731
        re.search(r"(^|,)2048(,|$)", s) and "2688" in s)
    assert not any(wide(s) for s in shapes), sorted(s for s in shapes if wide(s))
    assert any(s.endswith("2688,1856") for s in shapes)          # the experts at their own width


@pytest.mark.slow
def test_ssm_reference_step_fits(topo):
    """The float32 reference's own step (weights, Adam's state and gradients
    at 4 bytes each, the recurrence token by token in recomputed blocks)
    beside nothing else on the chip."""
    from jax.sharding import SingleDeviceSharding
    from benchmarks.adapters import gpt_tree, ssm_tree
    from benchmarks.reference import ssm_ref

    d = ssm_ref.dims(cell_config())
    one = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    w = jax.eval_shape(lambda k: ssm_ref.make_weights(d, k),
                       jax.ShapeDtypeStruct((2,), jnp.uint32))
    opt = jax.eval_shape(ssm_ref.adam_init, w)
    bias = jax.eval_shape(lambda: ssm_ref.bias_init(d))
    rows = jax.ShapeDtypeStruct((2, SEQ), jnp.int32, sharding=one)

    def step(w, opt, bias, tokens, targets):     # as the adapter's check runs it
        w, opt, bias, loss, g, counts = ssm_ref.train_step(w, opt, bias, d, tokens, targets,
                                                          lr=1e-5)
        return w, opt, bias, loss, counts, gpt_tree.leaf_norms(ssm_tree.to_program(g, d))

    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            place(w), place(opt), place(bias), rows, rows).compile()
    m = compiled.memory_analysis()
    print(f"\nreference: arguments {m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB, total {used(m) / 1e9:.3f} GB "
          f"({used(m) / 2 ** 30:.2f} of 15.75 GiB)")
    assert used(m) < HBM


@pytest.mark.slow
def test_ssm_reference_starting_bias_fits(topo):
    """The reference's pass that brings the starting bias to rest (float32
    weights made inside it, no gradient), before the program's state exists."""
    from jax.sharding import SingleDeviceSharding
    from benchmarks.reference import ssm_ref

    config = cell_config()
    d = ssm_ref.dims(config)
    one = SingleDeviceSharding(topo.devices[0])

    def balanced(key, tokens):
        return ssm_ref.balanced_bias(ssm_ref.make_weights(d, key), d, tokens,
                                     *config["engine"]["bias_balance"])

    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(balanced).lower(
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one),
            jax.ShapeDtypeStruct((2, SEQ), jnp.int32, sharding=one)).compile()
    m = compiled.memory_analysis()
    print(f"\nstarting bias: total {used(m) / 1e9:.3f} GB ({used(m) / 2 ** 30:.2f} of 15.75 GiB)")
    assert used(m) < HBM


# hidden (the rows' width), the width it moves at, an expert's width: the
# cell's own first, then other widths the loosened rules let onto the kernels
OTHER_WIDTHS = [(2688, 4096, 1856), (1536, 2048, 192), (3072, 4096, 960), (5120, 6144, 1344)]


@pytest.mark.slow
@pytest.mark.parametrize("hidden,moves_at,width", OTHER_WIDTHS)
def test_an_expert_layer_compiles_at_half_lane_tiles_and_widened_rows(
        topo, as_on_tpu, hidden, moves_at, width):
    """PR 38 loosened two shape rules for every caller: ``moe._gmm_shapes_ok``
    (whole HALF lane tiles) and ``moe._rows_width`` (a row the DMA cannot take
    moves at the next width it takes, under twice its own). Under
    ``impl="auto"`` such widths now reach Mosaic where they took XLA's
    fallback: a relu2 expert layer, forward and every gradient, compiles for
    the chip on the ``moe_gmm*`` and ``moe_rows_*`` kernels at widths other
    than the cell's, the rows at ``moves_at``. (The numbers at such widths:
    ``tests/test_grouped_matmul.py`` and ``tests/test_expert_rows.py``,
    interpreted; on the chip the cell's own alone, PERF.md section 7.)"""
    from jax.sharding import SingleDeviceSharding
    from apex_tpu.transformer import moe

    tokens, held, top_k = 2048, 8, 2
    one = SingleDeviceSharding(topo.devices[0])
    leaf = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one)  # noqa: E731
    p = {"router": leaf(hidden, held), "w_up": leaf(held, hidden, width),
         "w_down": leaf(held, width, hidden), "shared_up": leaf(hidden, 2 * width),
         "shared_down": leaf(2 * width, hidden)}
    assert moe._rows_width(leaf(tokens, hidden)) == moves_at
    assert moe._rows_impl("auto", leaf(tokens, hidden)) == "pallas"
    assert moe._gmm_shapes_ok(leaf(tokens, hidden), p["w_up"])

    def loss(p, x):
        y, _ = moe.dropless_moe_layer(p, x, top_k=top_k, score="sigmoid", shared_gate=False,
                                      activation="relu2")
        return jnp.sum(y.astype(jnp.float32))

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
    text = step.lower(p, leaf(tokens, hidden)).compile().as_text()
    names = set(re.findall(r"%((?:moe_gmm|moe_rows)[a-z_]*?)\.?\d* = ", text))
    print(f"\nhidden {hidden} (rows at {moves_at}), experts of {width}: {sorted(names)}")
    assert names == {n for n in KERNELS if n.startswith("moe_")}
    shapes = set(re.findall(r"bf16\[([\d,]+)\]", text))
    assert any(s.endswith(f"{hidden},{width}") for s in shapes)      # the experts at their width
