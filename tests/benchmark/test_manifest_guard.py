"""No pin of the benchmark's own may break on a cell a later PR adds. A
scratch ninth cell is entered into a COPY of the real ``BENCHMARK.json`` the
way a cell-adding PR enters one — a configuration, a cell and a metric of its
own with their files, and the cell's name added to the lists of the metrics
whose spans it shares — and every manifest check of ``tests/benchmark/`` and
``benchmarks/tests/`` runs against the copy: the whole file's contract, the
names, the block metrics' table, and each cell test's ``check_manifest``.
A test that pins a position, a count or a whole list fails here, in the PR
that writes it, and not nine PRs later."""
import copy
import importlib
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for path in (ROOT, os.path.join(ROOT, "tests", "benchmark")):
    if path not in sys.path:
        sys.path.insert(0, path)

from benchmarks import run  # noqa: E402
from benchmarks.tests import test_harness, test_scope_metrics, toy  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
# every cell's test file, found by its name: a later PR's too
CELL_TESTS = sorted(f[:-3] for f in os.listdir(HERE)
                    if f.startswith("test_") and f.endswith("_cell.py"))
SHARED = ("mfu_pct", "attn_block_ms", "mlp_block_ms", "moe_gmm_ms", "moe_gmm_roofline_pct",
          "unembed_xent_ms", "optimizer_ms", "recompute_ms", "unscoped_ms")


@pytest.fixture(scope="module")
def ninth(tmp_path_factory):
    """``(manifest, root)``: the real manifest with a scratch cell entered,
    and a root that holds the files its entries name beside the real ones."""
    root = tmp_path_factory.mktemp("ninth")
    for part in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmarks", part), root / "benchmarks" / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    config = dict(toy.TOY_TRAIN, source="https://example.org/scratch/config.json",
                  reduced=["n_layer"], reduced_why={"n_layer": "a scratch cut"},
                  changed={}, assumed={}, deployment="none", aot_memory={})
    (root / "benchmarks" / "configs" / "scratch.json").write_text(json.dumps(config))
    (root / "benchmarks" / "layer_metrics" / "steps_done.scratch.py").write_text(
        'LAYER = "trainer step"\nUNIT = "steps"\nMOVES = "train_tokens_per_s"\n\n\n'
        'def read(run):\n    return run.get("steps")\n')
    m = copy.deepcopy(test_harness.manifest())
    m["configs"].append({"name": "scratch", "source": config["source"],
                         "file": "benchmarks/configs/scratch.json", "reduced": ["n_layer"],
                         "why": "a scratch configuration"})
    m["workloads"].append({"name": "scratch-cell", "config": "scratch",
                           "traffic": "packed-text-1k", "chips": 1, "why": "a scratch cell"})
    m["per_layer"].append({"name": "steps_done.scratch", "unit": "steps", "better": "higher",
                           "source": "program_counter", "layer": "trainer step",
                           "moves": "train_tokens_per_s", "workloads": ["scratch-cell"]})
    for p in m["per_layer"]:
        if p["name"] in SHARED:
            p["workloads"].append("scratch-cell")
    return m, str(root)


def test_the_copy_holds_a_ninth_cell_that_reports_its_own_and_the_shared_names(ninth):
    m, _ = ninth
    real = test_harness.manifest()
    assert len(m["workloads"]) == len(real["workloads"]) + 1
    reported = {p["name"] for p in run.metrics_of(m, "per_layer", m["workloads"][-1])}
    assert {"steps_done.scratch", "step_ms.train", "flash_fwd_ms"} | set(SHARED) <= reported
    assert "steps_done.scratch" not in {
        p["name"] for p in run.metrics_of(m, "per_layer", m["workloads"][0])}
    assert real == test_harness.manifest() and "scratch" not in json.dumps(real)   # a copy


def test_a_ninth_cell_breaks_no_rule_of_the_whole_file(ninth):
    m, root = ninth
    test_harness.check_contract(m, root=root)
    test_harness.check_names(m)
    test_scope_metrics.check_manifest(m)


@pytest.mark.parametrize("module", CELL_TESTS)
def test_a_ninth_cell_breaks_no_cell_tests_manifest_check(ninth, module):
    importlib.import_module(module).check_manifest(ninth[0])


def test_every_cell_test_is_found_and_gives_its_manifest_check():
    assert {"test_hybrid_cell", "test_afmoe_cell", "test_mla_cell", "test_ssm_cell",
            "test_loop_cell", "test_bailing_cell"} <= set(CELL_TESTS)
    for module in CELL_TESTS:
        assert callable(getattr(importlib.import_module(module), "check_manifest", None)), module
