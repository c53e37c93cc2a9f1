"""Block times from the scope paths of a raw profiler trace: ms a traced step,
mean over chips, of the device operations under each of the program's scopes
(``gpt/attn``, ``gpt/mlp``, ``gpt/unembed_xent``, ``amp/apply_master``, ...),
forward and backward apart, and the named kernels within each. ``PERF.md``
section 5's block columns are this script's output on the raw traces of the
two cells. Run by hand, off the chip, on a trace that still has its stats:

    python benchmarks/tests/scope_times.py <raw.xplane.pb> [traced steps, default 5]

The scope path is the stat ``tf_op`` of an operation's ``XEventMetadata``
(``jit(call)/amp/fwd_bwd/transpose(jvp(gpt/attn))/flash_bwd_packed_dq/...``),
which ``jax.profiler.ProfileData`` does not expose, so this reads the
protobuf itself, with the schema TensorFlow ships. A ``benchmark`` issue that
adds ``optimizer_ms`` or ``unembed_xent_ms`` moves this reading into
``trace_reduce.py``.
"""
import collections
import importlib.util
import os
import re
import sys

# innermost first: an operation under ``amp/fwd_bwd/.../gpt/attn`` is attention's
SCOPES = ("fused_adam/update", "amp/apply_master", "amp/unscale_check", "ddp/allreduce",
          "gpt/unembed_xent", "gpt/attn", "gpt/mlp", "gpt/embed", "amp/fwd_bwd")
KERNELS = ("flash", "xentropy")
DEVICE = re.compile(r"^/device:TPU:\d+$")
NO_SCOPE = "(no scope)"


def xplane_schema():
    """TensorFlow's copy of the ``.xplane.pb`` schema, loaded as a file: it
    needs protobuf alone, and importing ``tensorflow`` for it takes 10 s."""
    package = importlib.util.find_spec("tensorflow")
    if package is None:
        raise SystemExit("no tensorflow here to take the .xplane.pb schema from")
    path = os.path.join(package.submodule_search_locations[0],
                        "tsl", "profiler", "protobuf", "xplane_pb2.py")
    spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def scope_paths(plane):
    """``metadata_id`` -> the operation's ``tf_op``. A stat holds its string
    itself or, where the profiler interned it, the id of a stat metadata
    whose name is the string."""
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    out = {}
    for key, meta in plane.event_metadata.items():
        for stat in meta.stats:
            if stat_names.get(stat.metadata_id) == "tf_op":
                out[key] = stat.str_value or stat_names.get(stat.ref_value, "")
    return out


def scope_times(space, steps):
    """``{"chips", "blocks": {scope [fwd|bwd]: ms}, "kernels": {block :: kernel:
    ms}, "no_scope": {opcode-like name: ms}}`` from a parsed ``XSpace``."""
    blocks, kernels, bare = (collections.Counter() for _ in range(3))
    chips = 0
    for plane in space.planes:
        if not DEVICE.match(plane.name):
            continue
        chips += 1
        paths = scope_paths(plane)
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                path = paths.get(e.metadata_id, "")
                name = plane.event_metadata[e.metadata_id].name.split(" = ")[0].lstrip("%")
                family = name.rsplit(".", 1)[0]
                block = next((s for s in SCOPES if s in path), NO_SCOPE)
                if block == NO_SCOPE:
                    bare[family] += e.duration_ps
                elif block.startswith("gpt/"):
                    block += " bwd" if "transpose(" in path else " fwd"
                blocks[block] += e.duration_ps
                if any(k in name for k in KERNELS):
                    kernels[f"{block} :: {family}"] += e.duration_ps
    if not chips:
        raise SystemExit("no /device:TPU:<n> plane in the trace")
    to_ms = 1e-9 / steps / chips

    def ms(counter):
        return {k: v * to_ms for k, v in counter.most_common()}

    return {"chips": chips, "blocks": ms(blocks), "kernels": ms(kernels), "no_scope": ms(bare)}


def main(path, steps=5):
    space = xplane_schema().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = scope_times(space, int(steps))
    print(f"{path}: {out['chips']} chip(s), {steps} traced steps, ms a step")
    for block, t in out["blocks"].items():
        print(f"{block:28s} {t:9.3f}")
    print(f"{'sum':28s} {sum(out['blocks'].values()):9.3f}")
    for kernel, t in out["kernels"].items():
        print(f"   {kernel:60s} {t:9.3f}")
    print("no scope:", ", ".join(f"{k} {t:.3f}" for k, t in list(out["no_scope"].items())[:8]))


if __name__ == "__main__":
    main(*sys.argv[1:])
