"""What ``ling3-train-8k``'s limits were set from: on the chip, at the cell's
own size, the numbers the check compares — for sound runs of the program over
several seeds, and for the control (the plain reference computed in float8 in
the program's place) over a few — with the two numbers ``readings.py`` does not
print: the held experts' load gap and the selection bias's.

    python benchmarks/tests/readings_bailing.py --seeds 4 --control 2

The benchmark's own runs never run this, and it measures no window.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks import run  # noqa: E402


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seeds", type=int, default=4)
    p.add_argument("--control", type=int, default=2)
    p.add_argument("--base", type=int, default=2_600_000_001)
    a = p.parse_args()
    manifest = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell, config = run.find_cell(manifest, "ling3-train-8k")
    run.require_device(cell["chips"])
    run.keep_compile_cache()
    adapter, ctx = run.context(cell, config, a.base, 0.0)
    t = adapter.Trainer(ctx)
    steps = config["engine"]["check_steps"]
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
            print(f"seed {ctx['seed']} {name} held_load_gap = {adapter.load_gap(got, ref):.6g}")
            print(f"seed {ctx['seed']} {name} router_bias_gap = "
                  f"{adapter.bias_gap(got, ref, t.ref_dims, steps):.6g}", flush=True)


if __name__ == "__main__":
    main()
