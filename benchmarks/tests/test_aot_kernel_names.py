"""The step program compiled at real size for a described v5e names its
Mosaic custom-calls after the kernels: what ``trace_reduce`` will read as
operation names on the chip, found out without one, and which kernels each
cell's step reaches. Slow, like ``test_aot_sizes.py`` beside it, and not part
of the tier-1 run:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_aot_kernel_names.py -q -s
"""
import re

import jax
import jax.numpy as jnp
import pytest

from benchmarks.tests.test_aot_sizes import as_on_tpu, config, topo  # noqa: F401

CUSTOM_CALL = re.compile(r"%?([\w.\-]+) = [^\n]*? custom-call\([^\n]*custom_call_target=\"tpu_custom_call\"")


@pytest.mark.parametrize("name,seq,chips,kernels,reached", [
    # 2 flash kernels a layer (the forward, the one-pass backward since PR 25) + CE. Heads
    # of 128 take the fused projection + attention block (packed q|k|v), heads of 64 the
    # same block two heads to a lane tile (PR 42)
    ("starcoderbase-1b-train1", 8192, 1, 17,
     {"flash_fwd_packed", "flash_bwd_packed_fused", "xentropy_stats"}),
    ("gpt2-medium", 1024, 4, 49,
     {"flash_fwd_packed_pair", "flash_bwd_packed_pair_fused", "xentropy_stats"})])
def test_compiled_step_names_its_kernels(topo, as_on_tpu, name, seq, chips,  # noqa: F811
                                         kernels, reached):
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
        text = t.step.lower(*state, rows, rows).compile().as_text()
    finally:
        mesh_lib.destroy_model_parallel()
    names = CUSTOM_CALL.findall(text)
    spelt = {re.sub(r"\.\d+$", "", n) for n in names}
    print(f"\n{name}: {len(names)} Mosaic custom-calls, spelt {sorted(spelt)}")
    assert len(names) == kernels
    # XLA names an instruction after the last segment of its scope path:
    # under the program's spans that is the kernel's own name, and without a
    # span around it the transform's too (``jvp_flash_fwd_packed_``), so
    # readers match a part of the name. No kernel is left unnamed
    assert spelt == reached
