"""Adapter: the O2 training step of ``train_o2_hybrid`` — fp32 masters beside
the bf16 model (``amp`` O2), ``fused_adam``, the dynamic loss scaler, one
donated jit over ``mesh.shard_map`` — on ``HybridDecoderModel.loss_fn`` built
as the ``deepseek_v2`` decoder (latent attention, a leading dense layer,
softmax-routed experts beside shared ones, the balance term per sequence),
through the program's public API only. The feed, the window, the step call,
the load counters and the comparison are ``train_o2_dp``'s and
``train_o2_hybrid``'s, imported; what is here is the model, the map between
the two weight trees and the reference's readings.

Settings (the configuration file's ``engine``): ``rows_per_chip``, ``lr``,
``remat`` (true: every block recomputed in the backward pass), ``check_steps``,
``trace_steps``.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks import hybrid_work, mla_work
from benchmarks.adapters import gpt_tree, mla_tree, train_o2_dp, train_o2_hybrid
from benchmarks.adapters.train_o2_dp import ALL_NUMBERS, B1, compare, leaf_gaps  # noqa: F401
from benchmarks.adapters.train_o2_hybrid import first_steps, load_gap  # noqa: F401
from benchmarks.reference import mla_ref


class Trainer(train_o2_hybrid.Trainer):
    """``train_o2_dp.Trainer``'s feed and ``train_o2_hybrid.Trainer``'s step
    call around the ``deepseek_v2`` decoder's step."""

    def __init__(self, ctx, devices=None):  # noqa: D107 - builds its own step
        from apex_tpu import amp
        from apex_tpu.models import HybridDecoderConfig, HybridDecoderModel
        from apex_tpu.optimizers import fused_adam
        from apex_tpu.parallel import mesh as mesh_lib

        self.ctx = ctx
        self.key = mla_ref.seed_key(ctx["seed"])
        self.ref_dims = mla_ref.dims(ctx["config"])
        # what the window's result carries as ``dims``: the model's own sizes
        # and the attention layers as the accepted flash readers take them
        self.d = dict(self.ref_dims, **mla_tree.attention_view(self.ref_dims))
        self.engine = e = ctx["config"]["engine"]
        self.mix = ctx["mix"]["params"]
        self.seq = self.mix["seq"]
        # the model before the mesh: a program that lacks the latent mixer
        # refuses its settings here, before it has asked for anything
        self.model = model = HybridDecoderModel(HybridDecoderConfig(
            **mla_tree.config_kwargs(self.ref_dims, remat=e["remat"])))
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
            counters = {"expert_load": jax.lax.psum(aux["expert_load"], "dp"),
                        "dropped": jax.lax.psum(aux["dropped"], "dp")}
            return master, opt_state, scaler, loss, counters

        self.step = jax.jit(
            mesh_lib.shard_map(run, in_specs=(P(), P(), P(), P("dp"), P("dp")),
                               out_specs=(P(), P(), P(), P(), P())),
            donate_argnums=(0, 1, 2))

        def init_state(key):
            w = mla_tree.to_program(mla_ref.make_weights(self.ref_dims, key))
            master = amp.MasterWeights.create(w, self.policy)
            return (master, opt.init(master.master),
                    amp.init_loss_scaler("dynamic"))

        self.init_state = jax.jit(init_state, out_shardings=self.replicated)

        def moved(master, key):
            w0 = mla_tree.to_program(mla_ref.make_weights(self.ref_dims, key))
            return gpt_tree.leaf_norms(
                jax.tree.map(lambda a, b: a - b, master.master, w0))

        self.moved = jax.jit(moved)
        self.first_gradient = jax.jit(lambda opt_state: jax.tree.map(
            lambda a: a / (1 - B1),
            {"norm": gpt_tree.leaf_norms(opt_state.buffers["m"]),
             "projection": gpt_tree.leaf_projections(opt_state.buffers["m"])}))
        self.state = None
        self.feed = None
        self.counters = []
        self.dropped = 0


def setup(ctx):
    t = Trainer(ctx)
    first_steps(t, ctx)
    return t


def measure(t, ctx, tracer):
    """``train_o2_dp.window``, with ``train_o2_hybrid``'s load
    counters of its steps beside it and the operations a token required of
    THIS block at those loads."""
    run = train_o2_dp.window(t, ctx, tracer)
    run["expert_load"] = train_o2_hybrid._loads(t)
    run["dropped"] = t.dropped
    run["train_flops_per_token"] = mla_work.window_flops_per_token(run)
    run["expert_matmul_work"] = hybrid_work.window_expert_matmul_work(
        run, view=mla_work.expert_view)
    ctx["log"](f"window: {run['dropped']} local assignments dropped; largest held load a "
               f"layer and step {int(run['expert_load'].sum(-1).max())} rows")
    return run


def reference_readings(t, ctx, precision="float32"):
    """The same first steps through the plain reference, on one chip. Only
    norms, projections and the load counters leave each step."""
    d, steps = t.ref_dims, t.engine["check_steps"]
    first, count = d["experts_held"]
    program_norms = lambda tree: gpt_tree.leaf_norms(mla_tree.to_program(tree))  # noqa: E731

    def step(w, opt, tokens, targets):
        w, opt, loss, g, counts = mla_ref.train_step(
            w, opt, d, tokens, targets, lr=t.engine["lr"], precision=precision)
        g = mla_tree.to_program(g)
        return w, opt, loss, counts[:, first:first + count], {
            "norm": gpt_tree.leaf_norms(g), "projection": gpt_tree.leaf_projections(g)}

    def moved(w, key):
        return program_norms(jax.tree.map(
            lambda a, b: a - b, w, mla_ref.make_weights(d, key)))

    out = {"loss": [], "expert_load": []}
    with jax.default_matmul_precision("highest"):
        w = jax.jit(lambda k: mla_ref.make_weights(d, k))(t.key)
        opt = jax.jit(mla_ref.adam_init)(w)
        step = jax.jit(step, donate_argnums=(0, 1))
        for i in range(steps):
            tokens, targets = t.host_batch(i)
            w, opt, loss, loads, norms = step(w, opt, jnp.asarray(tokens),
                                              jnp.asarray(targets))
            out["loss"].append(float(loss))
            out["expert_load"].append(np.asarray(loads))
            if i == 0:
                out["first_gradient"] = jax.device_get(norms)
        out["moved"] = jax.device_get(jax.jit(moved)(w, t.key))
    out["expert_load"] = np.stack(out["expert_load"])
    return out


def finish(t, ctx):
    """Free the program's state, then follow the first steps with the plain
    reference and compare."""
    rows = []
    bad = sum(1 for v in t.window_losses if not np.isfinite(v))
    rows.append(("window_losses_not_finite", bad, 0))
    rows.append(("dropped_assignments", t.dropped, 0))
    rows.append(("train_step_executables_beyond_one", t.step._cache_size() - 1, 0))
    t.state = None
    ref = reference_readings(t, ctx)
    limits = ctx["config"]["limits"]
    for name, value, _ in compare(t.readings, ref, ALL_NUMBERS):
        if name.split("@")[0].split(".step")[0] not in limits:
            ctx["log"](f"reading (no limit in this configuration): {name} = {value:.6g}")
    rows.append(("held_load_gap", load_gap(t.readings, ref), limits["held_load_gap"]))
    rows += compare(t.readings, ref, limits)
    from apex_tpu.parallel import mesh as mesh_lib
    mesh_lib.destroy_model_parallel()
    return rows
