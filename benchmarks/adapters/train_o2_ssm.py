"""Adapter: the O2 training step of ``train_o2_afmoe`` — fp32 masters beside
the bf16 model (``amp`` O2; the state-space layers' ``A_log``, ``dt_bias`` and
``D`` stay float32), ``fused_adam``, the dynamic loss scaler, one donated jit
over ``mesh.shard_map``, the routers' selection bias carried as state beside
the masters and moved by the step's own load counts — on
``HybridDecoderModel.loss_fn`` built as the ``nemotron_h`` decoder (Mamba-2
state-space mixers, blocks of one half, ungated relu2 experts, attention
without gate, norms or rotary), through the program's public API only. The
feed, the window, the step call, the counters and the comparison are
``train_o2_dp``'s, ``train_o2_hybrid``'s and ``train_o2_afmoe``'s, imported;
what is here is the model, the map between the two weight trees and the
reference's readings.

Settings (the configuration file's ``engine``): ``rows_per_chip``, ``lr``,
``remat`` (true: every block recomputed in the backward pass, all but what
the mixers' and the experts' policies keep by name), ``check_steps``,
``trace_steps``, and ``bias_balance`` ``[iterations, first rate]``: the
selection bias the state starts from. A job that continues a checkpoint
starts with a bias in balance with its routers; random routers with a bias
of zero send an expert up to 2.6 times its share, and which ones the seed
decides. So the state's bias is what the balancing rule leaves after
``iterations`` moves on one batch drawn from the seed, its rate falling
geometrically from ``first rate`` to the step's own: state from the seed
like the weights, and like them made by the reference alone
(``ssm_ref.balanced_bias`` on ``ssm_ref.make_weights``, float32) and handed
to the program (``Trainer.start_bias``).
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks import hybrid_work, ssm_work
from benchmarks.adapters import gpt_tree, ssm_tree, train_o2_dp, train_o2_hybrid
from benchmarks.adapters.train_o2_afmoe import _take_counters, bias_gap, first_steps  # noqa: F401
from benchmarks.adapters.train_o2_dp import ALL_NUMBERS, B1, compare, leaf_gaps  # noqa: F401
from benchmarks.adapters.train_o2_hybrid import load_gap  # noqa: F401
from benchmarks.reference import ssm_ref


class Trainer(train_o2_hybrid.Trainer):
    """``train_o2_dp.Trainer``'s feed and ``train_o2_hybrid.Trainer``'s step
    call around the ``nemotron_h`` decoder's step; the state is (masters,
    optimizer state, scaler, selection bias)."""

    def __init__(self, ctx, devices=None):  # noqa: D107 - builds its own step
        from apex_tpu import amp
        from apex_tpu.models import HybridDecoderConfig, HybridDecoderModel
        from apex_tpu.optimizers import fused_adam
        from apex_tpu.parallel import mesh as mesh_lib
        from apex_tpu.transformer.moe import router_bias_update

        self.ctx = ctx
        self.key = ssm_ref.seed_key(ctx["seed"])
        self.ref_dims = d = ssm_ref.dims(ctx["config"])
        self.engine = e = ctx["config"]["engine"]
        self.mix = ctx["mix"]["params"]
        self.seq = self.mix["seq"]
        # what the window's result carries as ``dims``: the model's own sizes
        # and the attention layers as the accepted flash readers take them
        self.d = dict(d, **ssm_tree.attention_view(d))
        # the model before the mesh: a program that lacks the state-space
        # mixer refuses its settings here, before it has asked for anything
        self.model = model = HybridDecoderModel(HybridDecoderConfig(
            **ssm_tree.config_kwargs(d, remat=e["remat"])))
        self.mesh = mesh_lib.initialize_model_parallel(
            devices=devices or jax.devices()[:ctx["chips"]])
        self.n = self.mesh.devices.size
        self.rows = e["rows_per_chip"] * self.n
        self.tokens_per_step = self.rows * self.seq
        self.policy = amp.get_policy("O2")
        self.opt = opt = fused_adam(e["lr"])
        self.replicated = NamedSharding(self.mesh, P())
        self.by_row = NamedSharding(self.mesh, P("dp"))
        rate = d["load_balance_coeff"]

        def run(master, opt_state, scaler, bias, tokens, targets):
            (loss, aux), (grads, finite, scaler) = amp.scaled_value_and_grad(
                lambda p, a, b: model.loss_fn(p, a, b, return_aux=True, router_bias=bias),
                has_aux=True)(scaler, master.model, tokens, targets)
            grads = jax.lax.pmean(grads, "dp")
            loss = jax.lax.pmean(loss, "dp")
            updates, opt_state = opt.update(grads, opt_state, master.master)
            master = amp.apply_updates_with_master(master, updates,
                                                   grads_finite=finite)
            # the bias follows the step's own counts; a step the scaler
            # skipped moves it no more than it moved the weights
            counts = jax.lax.psum(aux["router_counts"], "dp")
            bias = jnp.where(finite, router_bias_update(bias, counts, rate), bias)
            counters = {"expert_load": jax.lax.psum(aux["expert_load"], "dp"),
                        "dropped": jax.lax.psum(aux["dropped"], "dp"),
                        "bias_spread": jnp.max(bias, -1) - jnp.min(bias, -1)}
            return master, opt_state, scaler, bias, loss, counters

        self.step = jax.jit(
            mesh_lib.shard_map(run, in_specs=(P(), P(), P(), P(), P("dp"), P("dp")),
                               out_specs=(P(), P(), P(), P(), P(), P())),
            donate_argnums=(0, 1, 2, 3))

        def weights(key):
            return ssm_tree.to_program(ssm_ref.make_weights(d, key), d)

        def balanced(key):               # the reference's own, before the state is there
            tokens = jax.random.randint(jax.random.fold_in(key, 1), (self.rows, self.seq), 0,
                                        d["vocab_size"] - 1)
            return ssm_ref.balanced_bias(ssm_ref.make_weights(d, key), d, tokens,
                                         *e["bias_balance"])

        self.balanced = jax.jit(balanced)

        def init_state(key, bias):
            master = amp.MasterWeights.create(weights(key), self.policy,
                                              keep_float32=model.float32_params)
            return (master, opt.init(master.master), amp.init_loss_scaler("dynamic"), bias)

        self.seeded = jax.jit(init_state, out_shardings=self.replicated)

        def from_seed(key):              # both sides start from the reference's bias
            with jax.default_matmul_precision("highest"):
                self.start_bias = jax.device_get(self.balanced(key))
            return self.seeded(key, self.start_bias)

        self.init_state = from_seed
        self.moved = jax.jit(lambda master, key: gpt_tree.leaf_norms(
            jax.tree.map(lambda a, b: a - b, master.master, weights(key))))
        self.first_gradient = jax.jit(lambda opt_state: jax.tree.map(
            lambda a: a / (1 - B1),
            {"norm": gpt_tree.leaf_norms(opt_state.buffers["m"]),
             "projection": gpt_tree.leaf_projections(opt_state.buffers["m"])}))
        self.state = None
        self.feed = None
        self.counters = []
        self.dropped = 0
        self.bias_spread = []


def setup(ctx):
    t = Trainer(ctx)
    first_steps(t, ctx)
    return t


def measure(t, ctx, tracer):
    """``train_o2_dp.window``, with the counters of its steps
    beside it and the operations a token required at those loads."""
    run = train_o2_dp.window(t, ctx, tracer)
    run["expert_load"] = _take_counters(t)
    run["dropped"] = t.dropped
    run["bias_spread"] = np.stack(t.bias_spread)
    run["train_flops_per_token"] = ssm_work.window_flops_per_token(run)
    run["expert_matmul_work"] = hybrid_work.window_expert_matmul_work(
        run, work=ssm_work.expert_matmul_work)
    ctx["log"](f"window: selection bias spread at its end {run['bias_spread'][-1].max():.4g}; "
               f"{run['dropped']} local assignments dropped")
    return run


def reference_readings(t, ctx, precision="float32"):
    """The same first steps through the plain reference, on one chip, the
    bias threaded through them. Only norms, projections, the load counters
    and the bias leave each step."""
    d, steps = t.ref_dims, t.engine["check_steps"]
    first, count = d["experts_held"]

    def step(w, opt, bias, tokens, targets):
        w, opt, bias, loss, g, counts = ssm_ref.train_step(
            w, opt, bias, d, tokens, targets, lr=t.engine["lr"], precision=precision)
        g = ssm_tree.to_program(g, d)
        return w, opt, bias, loss, counts[:, first:first + count], {
            "norm": gpt_tree.leaf_norms(g), "projection": gpt_tree.leaf_projections(g)}

    def moved(w, key):
        return gpt_tree.leaf_norms(ssm_tree.to_program(jax.tree.map(
            lambda a, b: a - b, w, ssm_ref.make_weights(d, key)), d))

    out = {"loss": [], "expert_load": []}
    with jax.default_matmul_precision("highest"):
        w = jax.jit(lambda k: ssm_ref.make_weights(d, k))(t.key)
        opt = jax.jit(ssm_ref.adam_init)(w)
        bias = jnp.asarray(t.start_bias)
        step = jax.jit(step, donate_argnums=(0, 1, 2))
        for i in range(steps):
            tokens, targets = t.host_batch(i)
            w, opt, bias, loss, loads, norms = step(w, opt, bias, jnp.asarray(tokens),
                                                    jnp.asarray(targets))
            out["loss"].append(float(loss))
            out["expert_load"].append(np.asarray(loads))
            if i == 0:
                out["first_gradient"] = jax.device_get(norms)
        out["moved"] = jax.device_get(jax.jit(moved)(w, t.key))
    out["expert_load"] = np.stack(out["expert_load"])
    out["router_bias"] = np.asarray(bias)
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
    # the step's state that is no parameter is held to the reference's like
    # the parameters: a configuration of this adapter names both limits
    rows.append(("held_load_gap", load_gap(t.readings, ref), limits["held_load_gap"]))
    rows.append(("router_bias_gap",
                 bias_gap(t.readings, ref, t.ref_dims, t.engine["check_steps"]),
                 limits["router_bias_gap"]))
    rows += compare(t.readings, ref, limits)
    from apex_tpu.parallel import mesh as mesh_lib
    mesh_lib.destroy_model_parallel()
    return rows
