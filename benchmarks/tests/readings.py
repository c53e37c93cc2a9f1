"""What the limits of the output check were set from: on the chip, at a
cell's own size, the numbers the check compares — for sound runs of the
program over many seeds, and for the control (the plain reference computed
in float8 in the program's place) over a few.

    python benchmarks/tests/readings.py --workload <cell> --seeds 12 --control 3

The benchmark's own runs never run this, and it measures no window.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks import run  # noqa: E402


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control", type=int, default=3)
    p.add_argument("--base", type=int, default=2_500_000_001)
    a = p.parse_args()
    manifest = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell, config = run.find_cell(manifest, a.workload)
    run.require_device(cell["chips"])
    run.keep_compile_cache()
    adapter, ctx = run.context(cell, config, a.base, 0.0)
    t = adapter.Trainer(ctx)
    for i in range(a.seeds):
        ctx["seed"] = a.base + 7919 * i
        adapter.first_steps(t, ctx)
        t.stop_feed()
        t.state = None
        who = {"program": t.readings}
        ref = adapter.reference_readings(t, ctx)
        if i < a.control:
            who["control"] = adapter.reference_readings(t, ctx, precision="float8")
        for name, got in who.items():
            for number, value, _ in adapter.compare(got, ref, adapter.ALL_NUMBERS):
                print(f"seed {ctx['seed']} {name} {number} = {value:.6g}", flush=True)


if __name__ == "__main__":
    main()
