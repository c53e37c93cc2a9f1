"""Adapter: the program's O2 data-parallel training step, as
``chip_smoke.trainer_phase`` builds it — fp32 masters beside the bf16 model
(``amp`` O2), ``fused_adam``, the dynamic loss scaler, ``pmean`` over ``dp``,
one donated jit over ``mesh.shard_map`` — on ``GPTModel.loss_fn``, dp = the
cell's chips, through the program's public API only.

Settings (the configuration file's ``engine``): ``rows_per_chip``, ``lr``,
``remat``, ``remat_policy``, ``scan_layers``, ``check_steps``,
``trace_steps``.
"""

import collections
import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks import flops
from benchmarks.adapters import gpt_tree
from benchmarks.reference import gpt_ref

B1 = 0.9  # fused_adam's default; the first gradient is m / (1 - B1)
IN_FLIGHT = 2  # steps queued on the chip behind the one the host waits for


class Trainer:
    """One compiled step with its state. Set-up builds it, drives it through
    the first steps and hands the same object to the window."""

    def __init__(self, ctx, devices=None):
        from apex_tpu import amp
        from apex_tpu.models import GPTConfig, GPTModel
        from apex_tpu.optimizers import fused_adam
        from apex_tpu.parallel import mesh as mesh_lib

        self.ctx = ctx
        self.key = gpt_ref.seed_key(ctx["seed"])
        self.d = gpt_ref.dims(ctx["config"])
        self.engine = e = ctx["config"]["engine"]
        self.mix = ctx["mix"]["params"]
        self.seq = self.mix["seq"]
        self.mesh = mesh_lib.initialize_model_parallel(
            devices=devices or jax.devices()[:ctx["chips"]])
        self.n = self.mesh.devices.size
        self.rows = e["rows_per_chip"] * self.n
        self.tokens_per_step = self.rows * self.seq
        self.model = GPTModel(GPTConfig(**gpt_tree.gpt_config_kwargs(
            self.d, remat=e["remat"], remat_policy=e.get("remat_policy", "full"),
            attention_impl="flash", scan_layers=e["scan_layers"])))
        self.policy = amp.get_policy("O2")
        self.opt = fused_adam(e["lr"])
        self.replicated = NamedSharding(self.mesh, P())
        self.by_row = NamedSharding(self.mesh, P("dp"))
        model, opt = self.model, self.opt

        def run(master, opt_state, scaler, tokens, targets):
            loss, (grads, finite, scaler) = amp.scaled_value_and_grad(
                model.loss_fn)(scaler, master.model, tokens, targets)
            grads = jax.lax.pmean(grads, "dp")
            loss = jax.lax.pmean(loss, "dp")
            updates, opt_state = opt.update(grads, opt_state, master.master)
            master = amp.apply_updates_with_master(master, updates,
                                                   grads_finite=finite)
            return master, opt_state, scaler, loss

        self.step = jax.jit(
            mesh_lib.shard_map(run, in_specs=(P(), P(), P(), P("dp"), P("dp")),
                               out_specs=(P(), P(), P(), P())),
            donate_argnums=(0, 1, 2))

        def init_state(key):
            w = gpt_tree.to_program(gpt_ref.make_weights(self.d, key))
            master = amp.MasterWeights.create(w, self.policy)
            return (master, opt.init(master.master),
                    amp.init_loss_scaler("dynamic"))

        self.init_state = jax.jit(init_state, out_shardings=self.replicated)

        def moved(master, key):
            w0 = gpt_tree.to_program(gpt_ref.make_weights(self.d, key))
            return gpt_tree.leaf_norms(
                jax.tree.map(lambda a, b: a - b, master.master, w0))

        self.moved = jax.jit(moved)
        self.first_gradient = jax.jit(lambda opt_state: jax.tree.map(
            lambda a: a / (1 - B1),
            {"norm": gpt_tree.leaf_norms(opt_state.buffers["m"]),
             "projection": gpt_tree.leaf_projections(opt_state.buffers["m"])}))

        def fingerprints(master):
            leaves = jax.tree.leaves(master.master)
            return jnp.stack([sum(jnp.sum(a) for a in leaves),
                              sum(jnp.sum(a * a) for a in leaves)])[None]

        self.fingerprints = jax.jit(mesh_lib.shard_map(
            fingerprints, in_specs=(P(),), out_specs=P("dp")))
        self.state = None
        self.feed = None

    # --- the feed: one host thread, batch i a pure function of (seed, i) -----

    def host_batch(self, index):
        gen = self.ctx["generator"]
        tokens, targets, _ = gen.batch(self.mix, self.ctx["seed"], index,
                                       self.rows, self.d["vocab_size"])
        return tokens, targets

    def start_feed(self, depth=IN_FLIGHT + 2):
        self.feed = queue.Queue(maxsize=depth)
        self._stop = threading.Event()

        def produce():
            index = 0
            while not self._stop.is_set():
                item = jax.device_put(self.host_batch(index), self.by_row)
                while not self._stop.is_set():
                    try:
                        self.feed.put(item, timeout=0.05)
                        break
                    except queue.Full:
                        pass
                index += 1

        self._thread = threading.Thread(target=produce, daemon=True)
        self._thread.start()

    def stop_feed(self):
        self._stop.set()
        self._thread.join(timeout=10)
        if self._thread.is_alive():
            raise RuntimeError("the feed thread did not stop")

    def one_step(self):
        tokens, targets = self.feed.get()
        *self.state, loss = self.step(*self.state, tokens, targets)
        return loss


def first_steps(t, ctx):
    """State from the seed, the feed, and the first steps through the
    window's own call, with the readings the output check compares."""
    t.key = gpt_ref.seed_key(ctx["seed"])
    t.state = list(t.init_state(t.key))
    t.start_feed()
    t.readings = {"loss": []}
    steps = t.engine["check_steps"]
    for i in range(steps):
        t0 = time.perf_counter()
        loss = float(t.one_step())
        ctx["log"](f"set-up step {i}: loss {loss:.6f} in "
                   f"{time.perf_counter() - t0:.3f} s")
        t.readings["loss"].append(loss)
        if i == 0:
            t.readings["first_gradient"] = jax.device_get(
                t.first_gradient(t.state[1]))
    t.readings["moved"] = jax.device_get(t.moved(t.state[0], t.key))


def setup(ctx):
    t = Trainer(ctx)
    first_steps(t, ctx)
    if t.n > 1:  # compile it now, not after the window
        jax.block_until_ready(t.fingerprints(t.state[0]))
    return t


def window(t, ctx, tracer):
    """Steps for ``seconds``, every one counted, over the time from the
    first dispatch to the last loss fetched. The host fetches the loss of the
    step ``IN_FLIGHT`` behind the one it dispatched last, as a training loop
    that logs its loss does, so the chip has that many steps queued and a
    host that stops for less than their length costs nothing. With a tracer,
    the first ``trace_steps`` steps are traced and timed one by one."""
    seconds = ctx["seconds"]
    losses, step_s = [], []
    t0 = time.perf_counter()
    if tracer is not None:
        tracer.start()
        for _ in range(t.engine["trace_steps"]):
            s0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench_step"):
                losses.append(float(t.one_step()))
            step_s.append(time.perf_counter() - s0)
        tracer.stop()
    pending, fetched = collections.deque(), []

    def fetch():
        losses.append(float(pending.popleft()))
        fetched.append(time.perf_counter())

    while time.perf_counter() - t0 < seconds:
        pending.append(t.one_step())
        if len(pending) > IN_FLIGHT:
            fetch()
    while pending:
        fetch()
    elapsed = time.perf_counter() - t0
    gaps = np.diff(fetched)
    if len(gaps):
        # a step the chip finished late shows as a long wait for its loss
        ctx["log"](f"window: losses arrived {np.median(gaps):.4f} s apart (median), "
                   f"longest {gaps.max():.4f} s (loss {int(np.argmax(gaps)) + 1} of "
                   f"{len(losses)}), "
                   f"{int(np.sum(gaps > 1.2 * np.median(gaps)))} over 1.2 x the median")
    if tracer is not None:
        elapsed -= tracer.overhead_s
    t.stop_feed()
    t.window_losses = losses
    tokens = len(losses) * t.tokens_per_step
    return {
        "end_to_end": {"train_tokens_per_s": tokens / elapsed / t.n},
        "attempted": len(losses),
        "failed": sum(1 for v in losses if not np.isfinite(v)),
        "window_s": elapsed, "steps": len(losses), "step_s": step_s,
        "tokens": tokens, "chips": t.n, "seq": t.seq, "dims": t.d,
    }


def measure(t, ctx, tracer):
    """``window`` with the GPT block's required operations a token, which the
    MFU reader takes. An adapter of another block calls ``window`` and hands
    its own count: one that hands none reports no ``mfu_pct``."""
    run = window(t, ctx, tracer)
    run["train_flops_per_token"] = flops.train_flops_per_token(t.d, t.seq)
    return run


def leaf_gaps(got, want, scale=None):
    """For every tensor, the gap between two readings (signed: got - want)
    against the reference's norm of that tensor (``scale``; the readings
    themselves where they are norms) or of the median tensor, whichever is
    larger; with the tensors' names."""
    names = sorted(want)
    flat = lambda tree: np.concatenate([np.ravel(tree[n]) for n in names])  # noqa: E731
    ref, mine = flat(want), flat(got)
    norm = ref if scale is None else flat(scale)
    labels = [f"{n}[{i}]" for n in names for i in range(np.size(want[n]))]
    return (mine - ref) / np.maximum(norm, np.median(norm)), labels


def worst_leaf_gap(got, want):
    gaps, labels = leaf_gaps(got, want)
    worst = int(np.argmax(np.abs(gaps)))
    return float(abs(gaps[worst])), labels[worst]


def reference_readings(t, ctx, precision="float32"):
    """The same first steps through the plain reference, on one chip. Only
    norms leave each step, so the gradients are never held beside the state."""
    d, steps = t.d, t.engine["check_steps"]
    block = max(1, min(t.rows, 8192 // t.seq))
    program_norms = lambda tree: gpt_tree.leaf_norms(gpt_tree.to_program(tree))  # noqa: E731

    def step(w, opt, tokens, targets):
        w, opt, loss, g = gpt_ref.train_step(
            w, opt, d, tokens, targets, lr=t.engine["lr"], precision=precision,
            row_block=block)
        g = gpt_tree.to_program(g)
        return w, opt, loss, {"norm": gpt_tree.leaf_norms(g),
                              "projection": gpt_tree.leaf_projections(g)}

    def moved(w, key):
        return program_norms(jax.tree.map(
            lambda a, b: a - b, w, gpt_ref.make_weights(d, key)))

    out = {"loss": []}
    with jax.default_matmul_precision("highest"):
        w = jax.jit(lambda k: gpt_ref.make_weights(d, k))(t.key)
        opt = jax.jit(gpt_ref.adam_init)(w)
        step = jax.jit(step, donate_argnums=(0, 1))
        for i in range(steps):
            tokens, targets = t.host_batch(i)
            w, opt, loss, norms = step(w, opt, jnp.asarray(tokens),
                                       jnp.asarray(targets))
            out["loss"].append(float(loss))
            if i == 0:
                out["first_gradient"] = jax.device_get(norms)
        out["moved"] = jax.device_get(jax.jit(moved)(w, t.key))
    return out


ALL_NUMBERS = {"loss_gap": 0, "first_gradient_norm_gap": 0,
               "first_gradient_projection_gap": 0, "moved_norm_gap": 0}


def compare(readings, ref, limits):
    """(name, value, limit) for every number that has a limit: each step's
    loss; the first gradient's norm by the worst tensor; the first gradient's
    projection on a fixed random direction, root mean square over all tensors
    of the gap against the reference's norm of that tensor (a norm hardly
    shows rounding noise, a projection shows it in the first order); the
    norm of the parameters' change by the worst tensor."""
    rows = []
    if "loss_gap" in limits:
        rows += [(f"loss_gap.step{i}", abs(a - b), limits["loss_gap"])
                 for i, (a, b) in enumerate(zip(readings["loss"], ref["loss"]))]
    if "first_gradient_norm_gap" in limits:
        gap, where = worst_leaf_gap(readings["first_gradient"]["norm"],
                                    ref["first_gradient"]["norm"])
        rows.append((f"first_gradient_norm_gap@{where}", gap,
                     limits["first_gradient_norm_gap"]))
    if "first_gradient_projection_gap" in limits:
        gaps, _ = leaf_gaps(readings["first_gradient"]["projection"],
                            ref["first_gradient"]["projection"],
                            scale=ref["first_gradient"]["norm"])
        rows.append(("first_gradient_projection_gap",
                     float(np.sqrt(np.mean(gaps ** 2))),
                     limits["first_gradient_projection_gap"]))
    if "moved_norm_gap" in limits:
        gap, where = worst_leaf_gap(readings["moved"], ref["moved"])
        rows.append((f"moved_norm_gap@{where}", gap, limits["moved_norm_gap"]))
    return rows


def finish(t, ctx):
    """Free the program's state, then follow the first steps with the plain
    reference and compare."""
    rows = []
    bad = sum(1 for v in t.window_losses if not np.isfinite(v))
    rows.append(("window_losses_not_finite", bad, 0))
    if t.n > 1:
        prints = np.asarray(t.fingerprints(t.state[0]))
        rows.append(("chips_whose_parameters_differ",
                     int(np.sum(np.any(prints != prints[0], axis=1))), 0))
    cache = t.step._cache_size()
    rows.append(("train_step_executables_beyond_one", cache - 1, 0))
    t.state = None
    ref = reference_readings(t, ctx)
    limits = ctx["config"]["limits"]
    for name, value, _ in compare(t.readings, ref, ALL_NUMBERS):
        if name.split("@")[0].split(".step")[0] not in limits:
            ctx["log"](f"reading (no limit in this configuration): {name} = {value:.6g}")
    rows += compare(t.readings, ref, limits)
    from apex_tpu.parallel import mesh as mesh_lib
    mesh_lib.destroy_model_parallel()
    return rows
