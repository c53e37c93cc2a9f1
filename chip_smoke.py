"""chip_smoke.py — the system's two main paths, once, on the attached TPU.

    python chip_smoke.py

One process, five phases, in order; the first failure raises and the exit
code is non-zero (nothing here catches an exception):

1. device   — refuse anything but a TPU whose kind has a peak-FLOP/s row,
              with compiled (not interpreted, not disabled) Pallas kernels;
2. trainer  — the README's O2 data-parallel step (fp32 masters, FusedAdam,
              dynamic loss scaler, pmean over ``dp``, one donated jit over
              ``shard_map``) on ``GPTModel.loss_fn`` at the flagship's full
              width, dp = every local chip;
3. server   — ``ServingEngine.serve`` on the trained weights: mixed-length
              seeded requests, one prefix-cache hit, greedy parity against
              ``DecodeEngine.generate``, both jit caches pinned at one
              executable, block accounting clean after drain;
4. kernels  — ``tools/tpu_kernel_smoke.py``: every Pallas family compiled by
              Mosaic and checked against its XLA composition;
5. witness  — the lowered train and decode steps must CONTAIN the Mosaic
              custom calls (flash forward + backward, paged decode): the
              dispatch is a chain of silent conditions, and any of them
              turning false would hand the op to its jnp reference.

The last stdout line is one JSON object naming the device as JAX reports
it. Timings printed per phase are information only — no number from this
script is a benchmark metric. Compiled programs persist in the JAX
compilation cache (``JAX_COMPILATION_CACHE_DIR`` when set, otherwise
``.jax_cache/`` beside this file), so a second run compiles less.
"""

import importlib.metadata
import json
import math
import os
import statistics
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "tools"))

# the flagship (bench.py's on_tpu config): 186M parameters
FLAGSHIP = dict(vocab_size=32768, max_seq_len=1024, hidden_size=1024,
                num_layers=12, num_heads=8, tp_size=1, remat=False,
                attention_impl="flash", scan_layers=False)
# the largest per-chip batch of {20, 16, 8}: the O2 step (fp32 masters and
# moments beside the bf16 model, no remat) compiles to 2.6 GB of state plus
# 9.3 GB of temporaries at 20, inside one v5e's 15.75 GB
BATCH_PER_CHIP = 20
SEQ = 1024
TRAIN_STEPS = 6
SERVE = dict(num_slots=8, block_size=128, prefill_chunk=256,
             n_requests=10, prompt_range=(64, 512), new_range=(16, 64),
             shared_prefix=256)


def require(cond, message):
    if not cond:
        raise RuntimeError(f"chip_smoke: {message}")


# --- phase 1: device ----------------------------------------------------------

def device_phase(allow_cpu=False):
    """The device as JAX reports it, after refusing every configuration in
    which the later phases would check something other than the chip.
    ``allow_cpu`` (tests only) reports instead of refusing."""
    import jax
    import jaxlib

    from apex_tpu.monitor.report import PEAK_FLOPS_BY_DEVICE
    from apex_tpu.ops import _backend

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: platform={device['platform']} kind={device['kind']!r} "
          f"count={device['count']} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')}", flush=True)
    problems = []
    if dev.platform != "tpu":
        problems.append(f"platform is {dev.platform!r}, not 'tpu'")
    if dev.device_kind not in PEAK_FLOPS_BY_DEVICE:
        problems.append(f"device kind {dev.device_kind!r} has no row in "
                        f"PEAK_FLOPS_BY_DEVICE")
    if _backend.interpret_mode():
        problems.append("Pallas kernels would run in interpret mode")
    if os.environ.get("APEX_TPU_PALLAS") in ("0", "interpret"):
        problems.append(f"APEX_TPU_PALLAS={os.environ['APEX_TPU_PALLAS']} "
                        f"swaps every kernel")
    if problems and not allow_cpu:
        raise SystemExit("chip_smoke: refused — " + "; ".join(problems))
    return device


# --- phase 2: trainer ---------------------------------------------------------

def trainer_phase(cfg, batch_per_chip, seq, steps=TRAIN_STEPS, lr=1e-3):
    """≥ 5 O2 data-parallel steps on one fixed seeded batch. Returns the
    losses, the model, its trained bf16 params (replicated over the mesh),
    and the lowered step text for the witness phase."""
    import jax
    import jax.random as jr
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.optimizers import fused_adam
    from apex_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.initialize_model_parallel()          # dp = all chips
    n = mesh.devices.size
    model = GPTModel(GPTConfig(**cfg))
    policy = amp.get_policy("O2")                        # bf16 + fp32 masters
    opt = fused_adam(lr)

    def init_state():
        master = amp.MasterWeights.create(model.init(jr.PRNGKey(0)), policy)
        return (master, opt.init(master.master),
                amp.init_loss_scaler("dynamic"))

    def run(master, opt_state, scaler, tokens, targets):
        loss, (grads, finite, scaler) = amp.scaled_value_and_grad(
            model.loss_fn)(scaler, master.model, tokens, targets)
        grads = jax.lax.pmean(grads, "dp")               # the DDP all-reduce
        loss = jax.lax.pmean(loss, "dp")
        updates, opt_state = opt.update(grads, opt_state, master.master)
        master = amp.apply_updates_with_master(master, updates,
                                               grads_finite=finite)
        return master, opt_state, scaler, loss

    step = jax.jit(
        mesh_lib.shard_map(run, in_specs=(P(), P(), P(), P("dp"), P("dp")),
                           out_specs=(P(), P(), P(), P())),
        donate_argnums=(0, 1, 2))

    # One compiled init, born committed to the mesh: the step's outputs
    # come back committed, and an uncommitted -> committed change of the
    # inputs between the first and second call would be a second
    # executable.
    master, opt_state, scaler = jax.jit(
        init_state, out_shardings=NamedSharding(mesh, P()))()
    batch = batch_per_chip * n
    data = jr.randint(jr.PRNGKey(1), (batch, seq + 1), 0, cfg["vocab_size"])
    tokens, targets = jax.device_put(
        (data[:, :-1], data[:, 1:]), NamedSharding(mesh, P("dp")))

    text = step.lower(master, opt_state, scaler, tokens, targets).as_text()

    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        master, opt_state, scaler, loss = step(master, opt_state, scaler,
                                               tokens, targets)
        losses.append(float(loss))  # waits for the step
        times.append(time.perf_counter() - t0)
    run_s = statistics.median(times[1:])
    print(f"trainer: dp={n} batch={batch} ({batch_per_chip}/chip) seq={seq} "
          f"losses={[round(v, 4) for v in losses]}", flush=True)
    print(f"trainer: compile {times[0] - run_s:.1f} s, run {run_s:.3f} "
          f"s/step", flush=True)

    require(all(math.isfinite(v) for v in losses),
            f"non-finite trainer loss in {losses}")
    require(losses[-1] < losses[0],
            f"trainer loss did not decrease: {losses}")
    require(step._cache_size() == 1,
            f"train step compiled {step._cache_size()} executables, not 1")
    params = master.model
    mesh_lib.destroy_model_parallel()
    return dict(losses=losses, model=model, params=params, loss=loss,
                lowered_text=text, devices=list(mesh.devices.flat))


def require_on_all_devices(tree, devices):
    """Every leaf is addressable on every device of the mesh — code that
    only ever ran on a virtual mesh may have left everything on device 0."""
    import jax

    want = {d.id for d in devices}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        have = {s.device.id for s in leaf.addressable_shards}
        require(have == want,
                f"{jax.tree_util.keystr(path)} lives on devices "
                f"{sorted(have)}, not on all of {sorted(want)}")


def require_live_bytes(devices):
    """Every chip reports memory in use after the trainer ran."""
    for d in devices:
        in_use = d.memory_stats()["bytes_in_use"]
        require(in_use > 0, f"device {d.id} reports no bytes in use")
        print(f"trainer: device {d.id} holds {in_use / 2**30:.2f} GiB",
              flush=True)


# --- phase 3: server ----------------------------------------------------------

def serve_requests(vocab, *, n_requests, prompt_range, new_range,
                   shared_prefix, seed=0):
    """Seeded mixed-length requests; the first and the LAST share a
    ``shared_prefix``-token prefix — the last is admitted only after a
    slot frees, by which time the first one's prompt blocks are in the
    prefix cache, so exactly that admission is a guaranteed hit."""
    import numpy as np

    from apex_tpu.serving import Request

    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, shared_prefix).astype(np.int32)
    requests = []
    for rid in range(n_requests):
        length = int(rng.integers(prompt_range[0], prompt_range[1] + 1))
        prompt = rng.integers(0, vocab, length).astype(np.int32)
        if rid in (0, n_requests - 1):
            length = max(length, shared_prefix + 1)
            prompt = np.concatenate(
                [prefix, rng.integers(0, vocab, length - shared_prefix
                                      ).astype(np.int32)])
        requests.append(Request(
            rid=rid, prompt=prompt,
            max_new_tokens=int(rng.integers(new_range[0],
                                            new_range[1] + 1))))
    return requests


def server_phase(model, params, *, num_slots, block_size, prefill_chunk,
                 n_requests, prompt_range, new_range, shared_prefix):
    """Serve ≥ 8 requests through ``ServingEngine.serve``; returns the
    lowered decode-step text for the witness phase."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.inference import DecodeEngine
    from apex_tpu.serving import Request, ServingEngine

    vocab = model.config.vocab_size
    params = jax.device_put(params, jax.devices()[0])  # tp=1: one chip
    engine = ServingEngine(model, num_slots=num_slots, block_size=block_size,
                           prefill_chunk=prefill_chunk,
                           cache_dtype=jnp.bfloat16)
    require(n_requests > num_slots, "the prefix partner must queue")

    # one short request first: it compiles both programs, so the timed
    # serve below is all run time
    t0 = time.perf_counter()
    engine.serve(params, [Request(
        rid=-1, prompt=np.arange(prompt_range[0], dtype=np.int32) % vocab,
        max_new_tokens=2)])
    compile_s = time.perf_counter() - t0

    requests = serve_requests(
        vocab, n_requests=n_requests, prompt_range=prompt_range,
        new_range=new_range, shared_prefix=shared_prefix)
    want_tokens = {r.rid: r.max_new_tokens for r in requests}
    prompts = {r.rid: r.prompt for r in requests}
    sched = engine.make_scheduler()
    t0 = time.perf_counter()
    done = engine.serve(params, requests, scheduler=sched)
    run_s = time.perf_counter() - t0
    stats = engine.last_stats
    print(f"server: {len(done)} requests, "
          f"{sum(len(r.tokens) for r in done)} tokens, "
          f"{stats.prefill_chunks} prefill chunks, {stats.decode_steps} "
          f"decode steps, prefix hits "
          f"{[r.rid for r in done if r.prefix_hit_blocks]}", flush=True)
    print(f"server: compile {compile_s:.1f} s, run {run_s:.2f} s",
          flush=True)

    require(len(done) == n_requests,
            f"{len(done)} of {n_requests} requests finished")
    for r in done:
        require(len(r.tokens) == want_tokens[r.rid],
                f"request {r.rid} produced {len(r.tokens)} tokens, wanted "
                f"{want_tokens[r.rid]}")
        require(all(0 <= t < vocab for t in r.tokens),
                f"request {r.rid} produced an out-of-vocab token")
    require(sum(1 for r in done if r.prefix_hit_blocks > 0) == 1,
            "expected exactly one prefix-cache hit (the shared-prefix "
            "partner)")
    require(engine.prefill_chunk._cache_size() == 1
            and engine.decode_step._cache_size() == 1,
            f"serve jit caches: prefill_chunk "
            f"{engine.prefill_chunk._cache_size()}, decode_step "
            f"{engine.decode_step._cache_size()} — both must be 1")
    sched.allocator.check_accounting()
    require(sched.allocator.leaked == 0, "block pool leaked after drain")

    # greedy parity against the contiguous-cache engine, on the request
    # with the fewest new tokens
    ref = min(done, key=lambda r: (len(r.tokens), r.rid))
    single = DecodeEngine(model, cache_dtype=jnp.bfloat16)
    got = np.asarray(single.generate(
        params, jnp.asarray(prompts[ref.rid])[None], len(ref.tokens)))[0]
    require(list(got) == list(ref.tokens),
            f"greedy parity broke on request {ref.rid}: served "
            f"{list(ref.tokens)} vs DecodeEngine {list(got)}")
    print(f"server: request {ref.rid} ({len(prompts[ref.rid])}-token "
          f"prompt, {len(ref.tokens)} new) matches DecodeEngine.generate",
          flush=True)

    slots = jax.ShapeDtypeStruct((num_slots,), jnp.int32)
    text = engine.decode_step.lower(
        params, jax.eval_shape(engine.init_pool),
        jax.ShapeDtypeStruct((num_slots, engine.max_blocks_per_slot),
                             jnp.int32),
        slots, slots, jax.random.PRNGKey(0)).as_text()
    return dict(done=done, lowered_text=text)


# --- phase 5: witness ---------------------------------------------------------

def witness_phase(train_text, decode_text):
    """The Mosaic custom call, by kernel name, in the lowered programs."""
    import re

    def kernels(text):
        calls = re.findall(r"stablehlo\.custom_call @tpu_custom_call\(.*",
                           text)
        return sorted({m for c in calls
                       for m in re.findall(r'kernel_name = "([^"]+)"', c)})

    train, decode = kernels(train_text), kernels(decode_text)
    print(f"witness: train step Mosaic kernels {train}; decode step "
          f"{decode}", flush=True)
    # by part of the name, as the benchmark's readers match: the variant
    # (flash_fwd_packed, flash_bwd_packed_fused, ...) follows the shapes
    require(any("flash_fwd" in k for k in train),
            "flash forward kernel missing from the train step")
    require(any("flash_bwd" in k for k in train),
            "flash backward kernel missing from the train step")
    require("decode_attn_paged" in decode,
            "paged decode attention kernel missing from the decode step")


# --- entry --------------------------------------------------------------------

def main():
    from apex_tpu.utils.compile_cache import enable_compile_cache

    device = device_phase()
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    trained = trainer_phase(FLAGSHIP, BATCH_PER_CHIP, SEQ)
    if device["count"] > 1:
        require_on_all_devices((trained["loss"], trained["params"]),
                               trained["devices"])
        require_live_bytes(trained["devices"])

    served = server_phase(trained["model"], trained["params"], **SERVE)

    import tpu_kernel_smoke
    failed = tpu_kernel_smoke.main()
    require(not failed, f"kernel families disagree with XLA: {failed}")

    witness_phase(trained["lowered_text"], served["lowered_text"])

    print(f"chip_smoke: {device['kind']} x{device['count']} PASS")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
