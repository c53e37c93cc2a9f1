"""``ouro-train-8k``'s step program and its plain reference's step, each
compiled at real size for a described v5e: both fit, the program holds the
kernel families the cell's readers match (a flash pair a layer and WALK),
donates its state, and no exit's (tokens, vocabulary) logits stand beside
another's. Nothing runs; no chip is needed. Slow (each compiles for a minute
or two), not tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_aot_loop.py -q -s

``LOOP_LAYERS=6`` compiles another depth's try; the bytes of every try are in
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
ROOM = 0.5e9            # what the configuration's depth leaves under it
KERNELS = {"flash_fwd_bshd", "flash_bwd_bshd_fused", "xentropy_stats"}
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
    with open(os.path.join(HERE, "configs", "ouro-2.6b-train1.json")) as f:
        config = json.load(f)
    if os.environ.get("LOOP_LAYERS"):
        config["num_hidden_layers"] = int(os.environ["LOOP_LAYERS"])
    return config


def used(m):
    return (m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
            + m.temp_size_in_bytes)


@pytest.mark.slow
def test_loop_train_step_fits_and_holds_its_kernels(topo, as_on_tpu):
    from apex_tpu.parallel import mesh as mesh_lib
    from benchmarks.adapters import train_o2_loop

    config = cell_config()
    ctx = {"config": config, "mix": {"params": {"seq": SEQ}}, "chips": 1, "seed": 1}
    t = train_o2_loop.Trainer(ctx, devices=list(topo.devices[:1]))
    passes = config["num_hidden_layers"] * config["total_ut_steps"]
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
    names = set(re.findall(r"%((?:flash|xentropy)[a-z_]*?)\.?\d* = ", text))
    print(f"\n{config['num_hidden_layers']} layers: state {m.argument_size_in_bytes / 1e9:.3f} GB, "
          f"temporaries {m.temp_size_in_bytes / 1e9:.3f} GB, total {used(m) / 1e9:.3f} GB; "
          f"{text.count('tpu_custom_call')} Mosaic calls: {sorted(names)}")
    assert used(m) < HBM - ROOM
    assert m.alias_size_in_bytes >= 0.99 * m.output_size_in_bytes   # state donated
    assert names == KERNELS
    assert m.argument_size_in_bytes > 0.25 * 16e9      # the state alone passes the floor
    # a flash forward and a flash backward a layer and walk (since PR 41 the policy keeps the
    # forward's results: nothing of it runs again); the cross-entropy statistics once an
    # exit's block of tokens (its logits are made once, in the forward pass)
    calls = lambda name: len(re.findall(rf"%{name}\.?\d* = ", text))  # noqa: E731
    assert calls("flash_fwd_bshd") == passes and calls("flash_bwd_bshd_fused") == passes
    blocks = t.rows * SEQ // 8192                      # ``hybrid_decoder.EXIT_BLOCK`` tokens each
    assert calls("xentropy_stats") == config["total_ut_steps"] * blocks
    # an exit's logits stand in HBM a block of 8,192 tokens at a time, never
    # all of a step's tokens at once (and so never two exits' whole)
    shapes = set(re.findall(r"(?:bf16|f32)\[([\d,]+)\]", text))
    vocab = str(config["vocab_size"])
    assert f"8192,{vocab}" in shapes
    assert not {f"{t.rows},{SEQ},{vocab}", f"{t.rows * SEQ},{vocab}"} & shapes


@pytest.mark.slow
def test_loop_reference_step_fits(topo):
    """The float32 reference's own step (weights, Adam's state and gradients
    at 4 bytes each, a walk recomputed whole and inside it every block of
    every row) beside nothing else on the chip: the compiler takes it (it
    raises where a program does not fit: the first form, the walks unrolled
    and the rows mapped outside the layers' scan, was refused at 19.08 GiB).
    The sum of ``memory_analysis``'s sizes counts 2.1 GB more temporaries for
    this step than the buffer assignment the compiler allocates (buffers that
    never live at once), so what is held to the limit is the assignment's own
    peak, ``peak_memory_in_bytes`` (15.23 GB, my AOT dump, PR 40); the sum is
    printed beside it. The chip ran the step (my chip runs, PR 40)."""
    from jax.sharding import SingleDeviceSharding
    from benchmarks.adapters import gpt_tree, loop_tree
    from benchmarks.reference import loop_ref

    d = loop_ref.dims(cell_config())
    one = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    w = jax.eval_shape(lambda k: loop_ref.make_weights(d, k),
                       jax.ShapeDtypeStruct((2,), jnp.uint32))
    opt = jax.eval_shape(loop_ref.adam_init, w)
    rows = jax.ShapeDtypeStruct((2, SEQ), jnp.int32, sharding=one)

    def step(w, opt, tokens, targets):     # as the adapter's check runs it
        w, opt, loss, g, exits = loop_ref.train_step(w, opt, d, tokens, targets, lr=1e-5)
        return w, opt, loss, exits, gpt_tree.leaf_norms(loop_tree.to_program(g))

    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
            place(w), place(opt), rows, rows).compile()
    m = compiled.memory_analysis()
    peak = m.peak_memory_in_bytes
    print(f"\nreference: arguments {m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB, their sum {used(m) / 1e9:.3f} GB; the buffer "
          f"assignment's peak {peak / 1e9:.3f} GB ({peak / 2 ** 30:.2f} of 15.75 GiB)")
    assert 0.25 * 16e9 < peak < HBM
    assert m.alias_size_in_bytes >= 0.99 * m.output_size_in_bytes   # weights and state donated
