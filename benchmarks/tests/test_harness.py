"""The harness is driven by data: a configuration, a mix, a cell and a
per-layer metric added as new files and entries only are found and run; the
manifest keeps to the contract; a run without a TPU exits non-zero and
prints no result."""
import json
import os
import re
import shutil
import subprocess
import sys

import jax
import pytest

from benchmarks.tests import toy
from benchmarks import run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def manifest():
    return run.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def test_manifest_keeps_to_the_contract():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    cells = {w["name"]: w for w in m["workloads"]}
    configs = {c["name"]: c for c in m["configs"]}
    assert {w["config"] for w in m["workloads"]} == set(configs)
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) == len(cells)
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(1, len(cells) // 4)
    for c in m["configs"]:
        assert c["file"].startswith("benchmarks/") and os.path.exists(os.path.join(ROOT, c["file"]))
        on_file = run.load_json(os.path.join(ROOT, c["file"]))
        assert on_file["reduced"] == c["reduced"] and on_file["source"] == c["source"]
        assert set(on_file.get("reduced_why", {})) == set(c["reduced"])
        assert {"changed", "assumed", "deployment", "adapter", "engine", "limits",
                "aot_memory"} <= set(on_file)
    end = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in end and all(0 < e["bound"] <= 0.1 for e in m["end_to_end"])
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in m[k]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    for w in m["workloads"]:
        assert os.path.exists(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
        assert len(w["why"]) <= 200
        reported = run.metrics_of(m, "end_to_end", w)
        assert len(reported) >= 2 and run.metrics_of(m, "per_layer", w)
    for p in m["per_layer"]:
        assert p["moves"] in end
        reader = run.load_reader(p["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (p["layer"], p["unit"], p["moves"])
        for w in p.get("workloads", []):
            assert p["moves"] in {e["name"] for e in run.metrics_of(m, "end_to_end", cells[w])}


def test_new_files_and_entries_alone_add_a_cell(tmp_path):
    """A scratch configuration, mix, cell and layer metric; no file that
    exists is edited."""
    here = tmp_path / "bench"
    (here / "traffic").mkdir(parents=True)
    shutil.copytree(os.path.join(HERE, "layer_metrics"), here / "layer_metrics")
    (here / "configs").mkdir()
    (here / "configs" / "scratch.json").write_text(json.dumps(toy.TOY_TRAIN))
    mix = dict(toy.TOY_TRAIN_MIX, name="scratch-docs")
    (here / "traffic" / "scratch-docs.json").write_text(json.dumps(mix))
    (here / "layer_metrics" / "steps_done.scratch.py").write_text(
        'LAYER = "trainer step"\nUNIT = "steps"\nMOVES = "train_tokens_per_s"\n\n\n'
        'def read(run):\n    return run.get("steps")\n')
    m = toy.manifest()
    m["configs"] = [{"name": "scratch", "file": "configs/scratch.json"}]
    m["workloads"].append({"name": "scratch-cell", "config": "scratch",
                           "traffic": "scratch-docs", "chips": 1})
    m["per_layer"].append({"name": "steps_done.scratch", "unit": "steps",
                           "moves": "train_tokens_per_s", "workloads": ["scratch-cell"]})
    cell, config = run.find_cell(m, "scratch-cell", root=str(here))
    peaks = run.load_json(os.path.join(HERE, "peaks.json"))["TPU v5 lite"]
    line = run.execute(m, cell, config, toy.args(seed=1, trace=1), jax.devices()[:1],
                       peaks, here=str(here))
    assert line["correct"] is True
    assert line["metrics"]["steps_done.scratch"]["value"] == line["attempted"]
    other = run.metrics_of(m, "per_layer", m["workloads"][0])
    assert "steps_done.scratch" not in {p["name"] for p in other}


@pytest.mark.parametrize("bare", [False, True])
def test_without_a_tpu_it_exits_nonzero_and_prints_no_result(bare, tmp_path):
    root = ROOT
    if bare:   # only BENCHMARK.json and the files under paths
        root = str(tmp_path / "bare")
        os.makedirs(root)
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
        shutil.copytree(HERE, os.path.join(root, "benchmarks"),
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "sc1b-train-8k", "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=root, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert out.returncode != 0
    assert not any(l.startswith("{") for l in out.stdout.splitlines())
