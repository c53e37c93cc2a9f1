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


def check_contract(m, root=ROOT):
    """The rules of the whole file, asserted here once and by no cell's test:
    the keys, unique names, the four-chip share, the file's size, the check's
    time at this ``run_seconds``, and every entry against the files it names
    under ``root``."""
    here = os.path.join(root, "benchmarks")
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    # the window's length and the bounds as accepted: a later PR adds cells under them
    assert (m["run_seconds"], [(e["name"], e["bound"]) for e in m["end_to_end"]]) == (
        20, [("train_tokens_per_s", 0.01), ("setup_s", 0.1)])
    assert len(json.dumps(m, indent=1)) < 64 * 1024
    n = len(m["workloads"])
    assert 1 <= n <= 24 and (2 + 14 * n) * (m["run_seconds"] + 60) + 2 * 90 * n + 1200 <= 43200
    cells = {w["name"]: w for w in m["workloads"]}
    configs = {c["name"]: c for c in m["configs"]}
    assert {w["config"] for w in m["workloads"]} == set(configs)
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) == len(cells)
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(1, len(cells) // 4)
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/") and os.path.exists(os.path.join(root, c["file"]))
        on_file = run.load_json(os.path.join(root, c["file"]))
        assert on_file["reduced"] == c["reduced"] and on_file["source"] == c["source"]
        assert set(on_file.get("reduced_why", {})) == set(c["reduced"])
        assert {"changed", "assumed", "deployment", "adapter", "engine", "limits",
                "aot_memory"} <= set(on_file)
    end = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in end and all(0 < e["bound"] <= 0.1 for e in m["end_to_end"])
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in m[k]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert os.path.exists(os.path.join(here, "traffic", w["traffic"] + ".json"))
        assert len(w["why"]) <= 200
        reported = run.metrics_of(m, "end_to_end", w)
        assert len(reported) >= 2 and run.metrics_of(m, "per_layer", w)
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert p["moves"] in end
        reader = run.load_reader(p["name"], here)
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (p["layer"], p["unit"], p["moves"])
        assert len(set(p.get("workloads", []))) == len(p.get("workloads", []))
        for w in p.get("workloads", []):
            assert p["moves"] in {e["name"] for e in run.metrics_of(m, "end_to_end", cells[w])}


def test_manifest_keeps_to_the_contract():
    check_contract(manifest())


EVERY_TRAIN_CELL = {"flash_fwd_roofline_pct", "flash_bwd_roofline_pct", "flash_fwd_ms",
                    "flash_bwd_ms", "step_ms.train", "device_idle_pct.train", "peak_hbm_gb.train",
                    "xentropy_ms"}


def check_cell(m, name, config_name, metrics):
    """What every cell's test holds its own entries to, as MEMBERS of the
    manifest's lists: the cell and its configuration with the keys the
    contract names, each of ``metrics`` listing the cell and agreeing with its
    reader, the cell reporting them beside the list-less ones and the two
    end-to-end metrics. What a later PR put after them, or added to a list
    that holds this cell, is not looked at. Returns the cell, its
    configuration's file and entry, and the names it reports."""
    cell, config = run.find_cell(m, name)
    entry = next(c for c in m["configs"] if c["name"] == config_name)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (1, "packed-code-8k", config_name)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200
    listed = {p["name"]: p for p in m["per_layer"]}
    for metric in metrics:
        e = listed[metric]
        assert set(e) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert name in e["workloads"]
        reader = run.load_reader(metric)
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (e["layer"], e["unit"], e["moves"])
    reported = {p["name"] for p in run.metrics_of(m, "per_layer", cell)}
    assert set(metrics) | EVERY_TRAIN_CELL <= reported
    assert {e["name"] for e in run.metrics_of(m, "end_to_end", cell)} == {
        "train_tokens_per_s", "setup_s"}
    return cell, config, entry, reported


# what each cell reported at PR 46, the block-family suffixes (``.hybrid`` ``.afmoe`` ``.mla``
# ``.ssm`` ``.loop`` ``.bailing``) of that manifest's 39 twin entries stripped. The fold (PR 47)
# changed no cell's set; of these names a cell reports the same ones ever after, and a later
# PR's new names are free
EVERY_CELL = {"attn_block_ms", "device_idle_pct.train", "flash_bwd_ms", "flash_bwd_roofline_pct",
              "flash_fwd_ms", "flash_fwd_roofline_pct", "mfu_pct", "optimizer_ms",
              "peak_hbm_gb.train", "step_ms.train", "unembed_xent_ms", "xentropy_ms"}
REPORTED_AT_PR46 = {
    "sc1b-train-8k": {"attn_outside_kernels_ms", "mlp_block_ms", "unscoped_ms"},
    "gpt2m-train-1k-dp4": {"attn_outside_kernels_ms", "exposed_collective_ms", "mlp_block_ms",
                           "unscoped_ms"},
    "q3next-train-8k": {"attn_outside_kernels_ms", "delta_mixer_ms", "gdn_block_ms", "gdn_bwd_ms",
                        "gdn_bwd_roofline_pct", "gdn_fwd_ms", "gdn_fwd_roofline_pct",
                        "gdn_outside_kernels_ms", "mixer_place_ms", "mixer_proj_ms", "moe_block_ms",
                        "moe_gmm_ms", "moe_gmm_roofline_pct", "moe_load_max_over_mean",
                        "moe_route_ms", "moe_rows_ms", "recompute_ms", "unscoped_ms"},
    "trinity-train-8k": {"attn_outside_kernels_ms", "flash_win_bwd_ms",
                         "flash_win_bwd_roofline_pct", "flash_win_fwd_ms",
                         "flash_win_fwd_roofline_pct", "mixer_place_ms", "mixer_proj_ms",
                         "mlp_block_ms", "moe_block_ms", "moe_gmm_ms", "moe_gmm_roofline_pct",
                         "moe_load_max_over_mean", "moe_route_ms", "moe_rows_ms", "recompute_ms",
                         "unscoped_ms"},
    "dsv2lite-train-8k": {"attn_outside_kernels_ms", "mixer_place_ms", "mixer_proj_ms",
                          "mlp_block_ms", "moe_block_ms", "moe_gmm_ms", "moe_gmm_roofline_pct",
                          "moe_load_max_over_mean", "moe_route_ms", "moe_rows_ms", "unscoped_ms"},
    "nemotron3-train-8k": {"moe_block_ms", "moe_gmm_ms", "moe_gmm_roofline_pct",
                           "moe_load_max_over_mean", "moe_route_ms", "moe_rows_ms", "ssd_bwd_ms",
                           "ssd_bwd_roofline_pct", "ssd_fwd_ms", "ssd_fwd_roofline_pct",
                           "ssm_block_ms", "ssm_outside_kernels_ms"},
    "ouro-train-8k": {"attn_outside_kernels_ms", "exit_gate_ms", "exit_mass_last", "mlp_block_ms",
                      "recompute_ms", "unscoped_ms"},
    "ling3-train-8k": {"kda_block_ms", "kda_bwd_ms", "kda_bwd_roofline_pct", "kda_fwd_ms",
                       "kda_fwd_roofline_pct", "kda_outside_kernels_ms", "mlp_block_ms",
                       "moe_block_ms", "moe_gmm_ms", "moe_gmm_roofline_pct",
                       "moe_load_max_over_mean", "moe_route_ms", "recompute_ms",
                       "route_group_hit_share", "unscoped_ms"},
}
BLOCK_SUFFIXES = (".hybrid", ".afmoe", ".mla", ".ssm", ".loop", ".bailing")


def check_names(m):
    """One name a per-layer metric: none ends in a block's suffix; ``mfu_pct``
    lists every cell known at PR 46 and every cell reports a share of the
    chip's peak under a name that holds ``mfu``; of the names known at PR 46
    each of those cells reports exactly the ones it reported then."""
    names = [p["name"] for p in m["per_layer"]]
    assert not [n for n in names if n.endswith(BLOCK_SUFFIXES)]
    readers = {f[:-3] for f in os.listdir(os.path.join(HERE, "layer_metrics"))
               if f.endswith(".py") and f != "__init__.py"}
    assert not [n for n in readers if n.endswith(BLOCK_SUFFIXES)]
    known = EVERY_CELL.union(*REPORTED_AT_PR46.values())
    assert len(known) == 51 and known <= set(names)
    assert [p["name"] for p in m["per_layer"] if "mfu" in p["name"] and p["name"] in known] == [
        "mfu_pct"]
    for w in m["workloads"]:
        reported = {p["name"] for p in run.metrics_of(m, "per_layer", w)}
        assert any("mfu" in n for n in reported), w["name"]
        if w["name"] in REPORTED_AT_PR46:
            assert reported & known == EVERY_CELL | REPORTED_AT_PR46[w["name"]], w["name"]


def test_every_cell_reports_the_names_it_reported_before_the_fold():
    check_names(manifest())


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
