"""Every cell's step program lowered for a described v5e (nothing compiled,
nothing run, no chip needed), as one hash a cell: the text with the Mosaic
payloads and the source locations taken out — what a PR that must leave the
accepted cells' programs alone compares between its parent and itself.

    JAX_PLATFORMS=cpu python benchmarks/tests/lowered_steps.py [<checkout>] [<cell> ...]

Run it on a copy of the parent (``git archive <parent> | tar -x -C .scratch/parent``)
and on the working tree, and compare the lines. A cell whose adapter the
checkout cannot build (a configuration newer than its program) prints
``refused`` and the reason.
"""
import hashlib
import importlib
import json
import os
import re
import sys

ROOT = os.path.abspath(sys.argv[1]) if len(sys.argv) > 1 else os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def lowered(cell, manifest, topo):
    from apex_tpu.parallel import mesh as mesh_lib
    load = lambda *parts: json.load(open(os.path.join(ROOT, *parts)))  # noqa: E731
    config = load(next(c["file"] for c in manifest["configs"] if c["name"] == cell["config"]))
    mix = load("benchmarks", "traffic", cell["traffic"] + ".json")
    adapter = importlib.import_module("benchmarks.adapters." + config["adapter"])
    ctx = {"config": config, "mix": mix, "chips": cell["chips"], "seed": 1}
    t = adapter.Trainer(ctx, devices=list(topo.devices[:cell["chips"]]))
    try:
        state = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=t.replicated), jax.eval_shape(
                t.init_state, jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=t.replicated)))
        rows = jax.ShapeDtypeStruct((t.rows, t.seq), jnp.int32, sharding=t.by_row)
        return t.step.lower(*state, rows, rows).as_text()
    finally:
        mesh_lib.destroy_model_parallel()


def main():
    from jax.experimental import topologies
    from apex_tpu.ops import _backend
    _backend.backend_platform = lambda: "tpu"
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for cell in manifest["workloads"]:
        if sys.argv[2:] and cell["name"] not in sys.argv[2:]:
            continue
        try:
            text = lowered(cell, manifest, topo)
        except (TypeError, ValueError, ImportError) as e:
            print(f"{cell['name']} refused: {type(e).__name__}: {e}", flush=True)
            continue
        text = re.sub(r'backend_config = "[^"]*"', 'backend_config = "..."', text)
        text = re.sub(r"loc\([^)]*\)", "", text)
        print(f"{cell['name']} {len(text)} {hashlib.sha256(text.encode()).hexdigest()[:16]}",
              flush=True)


if __name__ == "__main__":
    main()
