"""chip_smoke.py off the chip: it must refuse a CPU before building any
model, its compile-cache placement must follow the environment, and its
trainer/server phases must run at toy size when a test calls them directly
(the device gate opened by an explicit argument of THIS test — the script
itself has no switch)."""

import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

import chip_smoke  # noqa: E402
from apex_tpu.utils import compile_cache  # noqa: E402

TOY = dict(vocab_size=128, max_seq_len=128, hidden_size=32, num_layers=1,
           num_heads=2, tp_size=1, remat=False, attention_impl="flash",
           scan_layers=False)
TOY_SERVE = dict(num_slots=2, block_size=16, prefill_chunk=32, n_requests=3,
                 prompt_range=(8, 40), new_range=(3, 5), shared_prefix=32)


def test_refuses_cpu_with_one_line_and_builds_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT,
                                                        "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          timeout=120)
    assert proc.returncode != 0
    refusal = [l for l in proc.stderr.splitlines() if "chip_smoke" in l]
    assert len(refusal) == 1 and "refused" in refusal[0]
    assert "platform is 'cpu'" in refusal[0]
    # the device line only: no phase ran, no result object
    assert "trainer:" not in proc.stdout and '"ok"' not in proc.stdout


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        want = os.path.join(ROOT, ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_phases_run_at_toy_size():
    device = chip_smoke.device_phase(allow_cpu=True)
    assert device["platform"] == "cpu" and device["count"] >= 1
    with pytest.raises(SystemExit, match="refused"):
        chip_smoke.device_phase()

    trained = chip_smoke.trainer_phase(TOY, 2, 32, steps=5, lr=1e-2)
    assert len(trained["losses"]) == 5
    assert trained["losses"][-1] < trained["losses"][0]
    chip_smoke.require_on_all_devices(
        (trained["loss"], trained["params"]), trained["devices"])

    served = chip_smoke.server_phase(trained["model"], trained["params"],
                                     **TOY_SERVE)
    assert len(served["done"]) == TOY_SERVE["n_requests"]

    # off the chip no Mosaic call exists, so the witness must refuse
    with pytest.raises(RuntimeError, match="flash forward kernel missing"):
        chip_smoke.witness_phase(trained["lowered_text"],
                                 served["lowered_text"])


@pytest.fixture(scope="module")
def lowered_for_tpu():
    """The train and decode steps lowered FOR the TPU, here on the CPU: the
    Mosaic custom calls carry the names the chip's programs will. Lowering
    needs no device; the kernels are picked as on the chip because the
    dispatch is steered here, in the test. Sizes: the smallest at which the
    dispatch hands attention to the flash kernels (heads of 128, 1,024
    positions)."""
    import jax.numpy as jnp

    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.ops import _backend
    from apex_tpu.serving import ServingEngine

    cfg = dict(TOY, vocab_size=512, max_seq_len=1024, hidden_size=256)
    patch = pytest.MonkeyPatch()
    patch.setattr(_backend, "backend_platform", lambda: "tpu")
    try:
        model = GPTModel(GPTConfig(**cfg))
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        rows = jax.ShapeDtypeStruct((2, 1024), jnp.int32)

        def lowered(fn, *args):
            return fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()

        engine = ServingEngine(model, num_slots=2, block_size=128,
                               prefill_chunk=128, cache_dtype=jnp.bfloat16)
        slots = jax.ShapeDtypeStruct((2,), jnp.int32)
        return dict(
            train=lowered(jax.jit(jax.value_and_grad(model.loss_fn)),
                          params, rows, rows),
            forward=lowered(jax.jit(model.loss_fn), params, rows, rows),
            decode=lowered(
                engine.decode_step, params, jax.eval_shape(engine.init_pool),
                jax.ShapeDtypeStruct((2, engine.max_blocks_per_slot),
                                     jnp.int32),
                slots, slots, jax.random.PRNGKey(0)))
    finally:
        patch.undo()


@pytest.mark.parametrize("train,decode,refusal", [
    ("train", "decode", None),
    ("decode", "decode", "flash forward kernel missing"),
    ("forward", "decode", "flash backward kernel missing"),
    ("train", "train", "paged decode attention kernel missing")])
def test_witness_reads_the_names_the_kernels_carry(lowered_for_tpu, train,
                                                   decode, refusal):
    """The witness matches the names ``ops/pallas`` gives its kernels; a
    rename there that the witness does not follow fails here, not on the
    chip at phase 5."""
    texts = lowered_for_tpu[train], lowered_for_tpu[decode]
    if refusal is None:
        chip_smoke.witness_phase(*texts)
    else:
        with pytest.raises(RuntimeError, match=refusal):
            chip_smoke.witness_phase(*texts)


def test_kernel_smoke_refuses_interpret_mode():
    import tpu_kernel_smoke

    with pytest.raises(RuntimeError, match="needs a TPU"):
        tpu_kernel_smoke.main()
