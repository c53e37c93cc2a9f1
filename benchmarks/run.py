"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that refuses anything but a TPU whose kind is in
``peaks.json``, keeps JAX's compilation cache inside the checkout, builds the
weights on the device from the seed, warms this cell's shapes, measures for
``--seconds`` and prints one JSON object as its last line. Everything else
it says goes on earlier lines.

Driven by data: the cell, its configuration and its traffic mix are entries
of ``BENCHMARK.json`` and files found by name — ``configs/<config>.json``
(which names its adapter), ``traffic/<mix>.json`` (which names its
generator), ``layer_metrics/<metric>.py``. A new cell needs no code here.
"""

import time

_T0 = time.time()  # process start, as nearly as Python can see it

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(message):
    print(message, flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def find_cell(manifest, name, root=ROOT):
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            break
    else:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    for config in manifest["configs"]:
        if config["name"] == cell["config"]:
            return cell, load_json(os.path.join(root, config["file"]))
    raise SystemExit(f"workload {name!r} names no known configuration")


def metrics_of(manifest, kind, cell):
    """The metrics of ``kind`` that this cell reports: those that list it,
    and of those that list no cells every end-to-end metric, and every
    per-layer metric whose end-to-end metric the cell reports."""
    listed = lambda m: "workloads" not in m or cell["name"] in m["workloads"]  # noqa: E731
    end_to_end = [m for m in manifest["end_to_end"] if listed(m)]
    if kind == "end_to_end":
        return end_to_end
    moved = {m["name"] for m in end_to_end}
    return [m for m in manifest["per_layer"] if listed(m) and m["moves"] in moved]


def require_device(chips):
    """The device as JAX reports it; exit without a result unless it is a
    TPU of a kind that has peaks, with the chips the cell asks for."""
    import jax

    peaks = load_json(os.path.join(HERE, "peaks.json"))
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devices)} jax={jax.__version__}")
    if dev.platform != "tpu":
        raise SystemExit(f"refused: platform is {dev.platform!r}, not 'tpu'")
    if dev.device_kind not in peaks:
        raise SystemExit(f"refused: no peaks for device kind {dev.device_kind!r}")
    if len(devices) < chips:
        raise SystemExit(f"refused: the cell asks for {chips} chips, "
                         f"JAX sees {len(devices)}")
    return devices[:chips], peaks[dev.device_kind]


def keep_compile_cache():
    """JAX's persistent cache at the program's fixed place inside the
    checkout, for every program however small or quick to compile."""
    import jax
    from apex_tpu.utils.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts XLA compilations (cache hits included) as JAX reports them."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1


class Tracer:
    """The profiler around part of the window, and the reduced trace."""

    def __init__(self, logdir):
        self.logdir = logdir
        self.overhead_s = 0.0
        self.window_s = None

    def start(self):
        import jax
        t0 = time.perf_counter()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.logdir, profiler_options=options)
        self._began = time.perf_counter()
        self.overhead_s += self._began - t0

    def stop(self):
        import jax
        t0 = time.perf_counter()
        self.window_s = t0 - self._began
        jax.profiler.stop_trace()
        self.overhead_s += time.perf_counter() - t0

    def reduced(self):
        from benchmarks import trace_reduce
        path = trace_reduce.newest_xplane(self.logdir)
        out = trace_reduce.reduce(trace_reduce.load(path))
        out["window_s"] = self.window_s
        out["file"] = path
        return out


def load_reader(name, here=HERE):
    """A per-layer metric's reader, found by the metric's name (which may
    hold dots, so by path and not by import)."""
    path = os.path.join(here, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("layer_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_layer_metrics(manifest, cell, run, here=HERE):
    out = {}
    for m in metrics_of(manifest, "per_layer", cell):
        value = load_reader(m["name"], here).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def context(cell, config, seed, seconds, here=HERE):
    """The cell's adapter, and what it is handed: the configuration, the
    traffic mix with its generator, the seed and the window's length."""
    mix = load_json(os.path.join(here, "traffic", cell["traffic"] + ".json"))
    adapter = importlib.import_module(f"benchmarks.adapters.{config['adapter']}")
    generator = importlib.import_module(f"benchmarks.generators.{mix['generator']}")
    return adapter, {"config": config, "mix": mix, "generator": generator,
                     "cell": cell, "seed": seed, "seconds": seconds,
                     "chips": cell["chips"], "log": log}


def execute(manifest, cell, config, args, devices, peaks, here=HERE):
    """Set-up, window, output check: everything after the look for a chip.
    Returns the result line as a dict."""
    import gc
    import shutil

    adapter, ctx = context(cell, config, args.seed, args.seconds, here)
    compiles = CompileCounter()
    state = adapter.setup(ctx)
    tracer = None
    if args.trace:
        # a directory of this process's own, removed once the trace is reduced
        logdir = os.path.join(ROOT, ".bench_trace", f"{cell['name']}.{os.getpid()}")
        tracer = Tracer(logdir)
    gc.collect()
    gc.freeze()
    compiled_before = compiles.count
    t_measure = time.time()
    run = adapter.measure(state, ctx, tracer)
    compiled_inside = compiles.count - compiled_before
    setup_s = t_measure - _T0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    log(f"window: {compiled_inside} compilations inside; set-up {setup_s:.2f} s; "
        f"peak memory {peak / 1e9:.2f} GB on the fullest chip")
    run.update(peaks=peaks, memory_peak_bytes=peak, config=config, cell=cell)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    line = {"attempted": run["attempted"], "failed": run["failed"], "device": device}
    if tracer is None:
        values = dict(run["end_to_end"], setup_s=setup_s)
        line["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics_of(manifest, "end_to_end", cell)}
    else:
        run["trace"] = trace = tracer.reduced()
        shutil.rmtree(logdir, ignore_errors=True)
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        line["metrics"] = read_layer_metrics(manifest, cell, run, here)
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    t_check = time.time()
    compared = [("compilations_inside_window", compiled_inside, 0)]
    compared += adapter.finish(state, ctx)
    ok = True
    for name, value, limit in compared:
        good = bool(value <= limit)
        ok = ok and good
        log(f"check: {name} = {value:.6g} (limit {limit:.6g}) "
            f"{'ok' if good else 'NOT CORRECT'}")
    log(f"check: took {time.time() - t_check:.1f} s")
    line["correct"] = ok and run["failed"] == 0
    return line


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config = find_cell(manifest, args.workload)
    devices, peaks = require_device(cell["chips"])
    log(f"compile cache: {keep_compile_cache()}")
    line = execute(manifest, cell, config, args, devices, peaks)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
