"""Adapter: the O2 training step of ``train_o2_hybrid`` — fp32 masters beside
the bf16 model (``amp`` O2), ``fused_adam``, the dynamic loss scaler, one
donated jit over ``mesh.shard_map`` — on ``HybridDecoderModel.loss_fn`` built
as the ``ouro`` decoder: ONE stack of sandwich-normed attention / SwiGLU
blocks walked ``total_ut_steps`` times on the same weights
(``HybridDecoderConfig.loop_trips``), an exit through the one head and a
learned gate after every walk, a loss over all the exits; through the
program's public API only. The feed, the window, the step call and the first
steps are ``train_o2_dp``'s and ``train_o2_hybrid``'s, imported; what is here
is the model, the map between the two weight trees, the reference's readings
and what the step hands back beside the loss: every exit's mean loss, the
exit distribution's mean and its entropy.

Settings (the configuration file's ``engine``): ``rows_per_chip``, ``lr``,
``remat`` (true: every block recomputed whole in the backward pass, all but
the results of its kernels), ``check_steps``, ``trace_steps``.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks import loop_work
from benchmarks.adapters import gpt_tree, loop_tree, train_o2_dp, train_o2_hybrid
from benchmarks.adapters.train_o2_dp import B1, leaf_gaps  # noqa: F401
from benchmarks.reference import loop_ref

EXITS = ("exit_losses", "exit_mass")


class Trainer(train_o2_hybrid.Trainer):
    """``train_o2_dp.Trainer``'s feed and ``train_o2_hybrid.Trainer``'s step
    call around the looped decoder's step."""

    def __init__(self, ctx, devices=None):  # noqa: D107 - builds its own step
        from apex_tpu import amp
        from apex_tpu.models import HybridDecoderConfig, HybridDecoderModel
        from apex_tpu.optimizers import fused_adam
        from apex_tpu.parallel import mesh as mesh_lib

        self.ctx = ctx
        self.key = loop_ref.seed_key(ctx["seed"])
        self.ref_dims = d = loop_ref.dims(ctx["config"])
        self.engine = e = ctx["config"]["engine"]
        self.mix = ctx["mix"]["params"]
        self.seq = self.mix["seq"]
        # what the window's result carries as ``dims``: the model's own sizes
        # and the attention calls as the accepted flash readers take them
        self.d = dict(d, **loop_tree.attention_view(d))
        # the model before the mesh: a program whose stack cannot loop refuses
        # its settings here, before it has asked for anything
        self.model = model = HybridDecoderModel(HybridDecoderConfig(
            **loop_tree.config_kwargs(d, remat=e["remat"])))
        self.mesh = mesh_lib.initialize_model_parallel(
            devices=devices or jax.devices()[:ctx["chips"]])
        self.n = self.mesh.devices.size
        self.rows = e["rows_per_chip"] * self.n
        self.tokens_per_step = self.rows * self.seq
        self.policy = amp.get_policy("O2")
        self.opt = opt = fused_adam(e["lr"])
        self.replicated = NamedSharding(self.mesh, P())
        self.by_row = NamedSharding(self.mesh, P("dp"))

        def run(master, opt_state, scaler, tokens, targets):
            (loss, aux), (grads, finite, scaler) = amp.scaled_value_and_grad(
                lambda p, a, b: model.loss_fn(p, a, b, return_aux=True),
                has_aux=True)(scaler, master.model, tokens, targets)
            grads = jax.lax.pmean(grads, "dp")
            loss = jax.lax.pmean(loss, "dp")
            updates, opt_state = opt.update(grads, opt_state, master.master)
            master = amp.apply_updates_with_master(master, updates,
                                                   grads_finite=finite)
            counters = {n: jax.lax.pmean(aux[n], "dp") for n in EXITS + ("exit_entropy",)}
            return master, opt_state, scaler, loss, counters

        self.step = jax.jit(
            mesh_lib.shard_map(run, in_specs=(P(), P(), P(), P("dp"), P("dp")),
                               out_specs=(P(), P(), P(), P(), P())),
            donate_argnums=(0, 1, 2))

        def weights(key):
            return loop_tree.to_program(loop_ref.make_weights(d, key))

        def init_state(key):
            master = amp.MasterWeights.create(weights(key), self.policy)
            return (master, opt.init(master.master), amp.init_loss_scaler("dynamic"))

        self.init_state = jax.jit(init_state, out_shardings=self.replicated)
        self.moved = jax.jit(lambda master, key: gpt_tree.leaf_norms(
            jax.tree.map(lambda a, b: a - b, master.master, weights(key))))
        self.first_gradient = jax.jit(lambda opt_state: jax.tree.map(
            lambda a: a / (1 - B1),
            {"norm": gpt_tree.leaf_norms(opt_state.buffers["m"]),
             "projection": gpt_tree.leaf_projections(opt_state.buffers["m"])}))
        self.state = None
        self.feed = None
        self.counters = []


def _take_counters(t):
    """{reading: (steps, ...)} of the steps since the counters were last taken."""
    got, t.counters = jax.device_get(t.counters), []
    return {n: np.stack([c[n] for c in got]) for n in got[0]} if got else {}


def first_steps(t, ctx):
    """``train_o2_dp.first_steps``, and the exits' readings of those steps."""
    t.counters = []
    train_o2_dp.first_steps(t, ctx)
    t.readings.update(_take_counters(t))


def setup(ctx):
    t = Trainer(ctx)
    first_steps(t, ctx)
    return t


def measure(t, ctx, tracer):
    """``train_o2_dp.window``, with the exits' readings of its steps
    beside it and the operations a token required."""
    run = train_o2_dp.window(t, ctx, tracer)
    run.update(_take_counters(t))
    run["train_flops_per_token"] = loop_work.window_flops_per_token(run)
    ctx["log"]("window: exits' share of the tokens at its end "
               + " ".join(f"{p:.4f}" for p in run["exit_mass"][-1])
               + f", entropy {run['exit_entropy'][-1]:.4f}")
    return run


def reference_readings(t, ctx, precision="float32"):
    """The same first steps through the plain reference, on one chip. Only
    norms, projections and the exits' readings leave each step."""
    d, steps = t.ref_dims, t.engine["check_steps"]

    def step(w, opt, tokens, targets):
        w, opt, loss, g, exits = loop_ref.train_step(
            w, opt, d, tokens, targets, lr=t.engine["lr"], precision=precision)
        g = loop_tree.to_program(g)
        return w, opt, loss, exits, {"norm": gpt_tree.leaf_norms(g),
                                     "projection": gpt_tree.leaf_projections(g)}

    def moved(w, key):
        return gpt_tree.leaf_norms(loop_tree.to_program(jax.tree.map(
            lambda a, b: a - b, w, loop_ref.make_weights(d, key))))

    out = {"loss": [], **{n: [] for n in EXITS}}
    with jax.default_matmul_precision("highest"):
        w = jax.jit(lambda k: loop_ref.make_weights(d, k))(t.key)
        opt = jax.jit(loop_ref.adam_init)(w)
        step = jax.jit(step, donate_argnums=(0, 1))
        for i in range(steps):
            tokens, targets = t.host_batch(i)
            w, opt, loss, exits, norms = step(w, opt, jnp.asarray(tokens),
                                              jnp.asarray(targets))
            out["loss"].append(float(loss))
            for n in EXITS:
                out[n].append(np.asarray(exits[n]))
            if i == 0:
                out["first_gradient"] = jax.device_get(norms)
        out["moved"] = jax.device_get(jax.jit(moved)(w, t.key))
    for n in EXITS:
        out[n] = np.stack(out[n])
    return out


ALL_NUMBERS = dict(train_o2_dp.ALL_NUMBERS, exit_losses_gap=0, exit_mass_gap=0)


def compare(readings, ref, limits):
    """``train_o2_dp.compare``'s rows and, where they have a limit, the two
    this objective adds: the worst exit's mean loss and the worst exit's share
    of the tokens (``exit_mass``, a probability), each the largest gap over
    the checked steps and the walks."""
    rows = train_o2_dp.compare(readings, ref, limits)
    for name in EXITS:
        if name + "_gap" in limits:
            gaps = np.abs(np.asarray(readings[name], np.float64) - np.asarray(ref[name]))
            step, walk = np.unravel_index(int(np.argmax(gaps)), gaps.shape)
            rows.append((f"{name}_gap@step{step}.exit{walk}", float(gaps.max()),
                         limits[name + "_gap"]))
    return rows


def finish(t, ctx):
    """Free the program's state, then follow the first steps with the plain
    reference and compare."""
    rows = []
    bad = sum(1 for v in t.window_losses if not np.isfinite(v))
    rows.append(("window_losses_not_finite", bad, 0))
    rows.append(("train_step_executables_beyond_one", t.step._cache_size() - 1, 0))
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
