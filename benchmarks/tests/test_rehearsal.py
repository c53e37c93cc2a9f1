"""Toy-size CPU rehearsals of the adapter through the harness's own
``execute`` (everything but the look for a chip): a sound run is correct,
a run with the timed path broken underneath is not, and the control — the
plain reference computed in float8 in the program's place — fails the same
comparison. The chip-size readings behind the real limits are in PERF.md;
``readings.py`` reproduces them."""
import json
import os
import subprocess
import sys

import jax
import pytest

from benchmarks.tests import toy
from benchmarks import run
from benchmarks.adapters import train_o2_dp

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
PEAKS = run.load_json(os.path.join(HERE, "peaks.json"))["TPU v5 lite"]


@pytest.fixture
def here(tmp_path):
    (tmp_path / "traffic").mkdir()
    os.symlink(os.path.join(HERE, "layer_metrics"), tmp_path / "layer_metrics")
    mix = toy.TOY_TRAIN_MIX
    (tmp_path / "traffic" / (mix["name"] + ".json")).write_text(json.dumps(mix))
    return str(tmp_path)


def execute(here, **kw):
    m = toy.manifest()
    return run.execute(m, m["workloads"][0], toy.TOY_TRAIN, toy.args(**kw),
                       jax.devices()[:1], PEAKS, here=here)


def test_train_rehearsal_is_correct_and_reports_the_contract_keys(here):
    line = execute(here, seed=2**31 + 77)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 3
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    json.dumps(line)


@pytest.mark.parametrize("entry,mfu", [("measure", True), ("window", False)])
def test_traced_train_rehearsal_reports_layer_metrics(here, monkeypatch, entry, mfu):
    """The shared ``window`` hands no ``train_flops_per_token``: an adapter of
    another block that forgets its own reads nothing, never the GPT count."""
    monkeypatch.setattr(train_o2_dp, "measure", getattr(train_o2_dp, entry))
    line = execute(here, seed=8, trace=1)
    assert "step_ms.train" in line["metrics"] and ("mfu_pct" in line["metrics"]) is mfu
    assert "device_idle_pct.train" not in line["metrics"]   # no device in a CPU trace
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_step_that_returns_its_state_unchanged_is_not_correct(here, monkeypatch):
    from apex_tpu import amp
    monkeypatch.setattr(amp, "apply_updates_with_master",
                        lambda weights, updates, grads_finite=None: weights)
    assert execute(here, seed=3)["correct"] is False


def test_a_step_that_leaves_out_part_of_the_batch_is_not_correct(here, monkeypatch):
    real = train_o2_dp.Trainer.host_batch
    calls = {"n": 0}

    def feed_only(self, index):          # the reference still draws the real rows
        tokens, targets = real(self, index)
        calls["n"] += 1
        if calls["n"] <= 3:               # the feed's first three batches
            tokens, targets = tokens.copy(), targets.copy()
            tokens[1:], targets[1:] = tokens[:1], targets[:1]
        return tokens, targets

    monkeypatch.setattr(train_o2_dp.Trainer, "host_batch", feed_only)
    assert execute(here, seed=4)["correct"] is False


def _ctx(config, mix, seed):
    import importlib
    return {"config": config, "mix": mix, "seed": seed, "seconds": 1.0, "chips": 1,
            "log": lambda m: None,
            "generator": importlib.import_module("benchmarks.generators." + mix["generator"])}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_fails_the_training_comparison(seed):
    ctx = _ctx(toy.TOY_TRAIN, toy.TOY_TRAIN_MIX, seed)
    t = train_o2_dp.Trainer(ctx)
    ref = train_o2_dp.reference_readings(t, ctx)
    low = train_o2_dp.reference_readings(t, ctx, precision="float8")
    rows = train_o2_dp.compare(low, ref, toy.TOY_TRAIN["limits"])
    assert any(value > limit for _, value, limit in rows)
    from apex_tpu.parallel import mesh as mesh_lib
    mesh_lib.destroy_model_parallel()


@pytest.mark.parametrize("broken", [False, True])
def test_four_virtual_chips_sound_and_without_the_exchange(broken, tmp_path):
    """dp = 4 on virtual CPU devices, in a process of its own; with pmean
    taken out the chips drift apart and the run is not correct."""
    script = f'''
import json, os, sys
sys.path.insert(0, {ROOT!r})
import jax
from benchmarks.tests import toy
from benchmarks import run
here = {str(tmp_path)!r}
os.makedirs(here + "/traffic")
os.symlink({os.path.join(HERE, "layer_metrics")!r}, here + "/layer_metrics")
json.dump(toy.TOY_TRAIN_MIX, open(here + "/traffic/toy-docs.json", "w"))
if {broken!r}:
    jax.lax.pmean = lambda x, axis: x
m = toy.manifest()
line = run.execute(m, dict(m["workloads"][0], chips=4), toy.TOY_TRAIN, toy.args(seed=9),
                   jax.devices()[:4], json.load(open({os.path.join(HERE, "peaks.json")!r}))["TPU v5 lite"],
                   here=here)
print(json.dumps(line))
'''
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", script], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert line["correct"] is (not broken)
