"""Adapter: the O2 training step of ``train_o2_afmoe`` — fp32 masters beside
the bf16 model (``amp`` O2; the delta-rule layers' ``A_log`` and ``dt_bias``
stay float32), ``fused_adam``, the dynamic loss scaler, one donated jit over
``mesh.shard_map``, the routers' selection bias carried as state beside the
masters and moved by the step's own load counts — on
``HybridDecoderModel.loss_fn`` built as the ``bailing_hybrid`` decoder
(delta-rule mixers with a decay a key channel five to one with a gated latent
mixer, group-limited sigmoid routing), through the program's public API only.
The feed, the window, the step call, the comparison and the bias's readings
are ``train_o2_dp``'s, ``train_o2_hybrid``'s and ``train_o2_afmoe``'s,
imported; what is here is the model, the map between the two weight trees, the
reference's readings and the two counters this block adds: the share of
tokens whose kept groups hold the held experts' (``router_group_hit``) and the
smallest per-step log decay a step took (``kda_log_decay_min``).

Settings (the configuration file's ``engine``): ``rows_per_chip``, ``lr``,
``remat`` (true: every block recomputed in the backward pass, all but what the
mixers' and the experts' policies keep by name), ``check_steps``,
``trace_steps``.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks import bailing_work, hybrid_work, mla_work
from benchmarks.adapters import bailing_tree, gpt_tree, train_o2_dp, train_o2_hybrid
from benchmarks.adapters.train_o2_afmoe import bias_gap
from benchmarks.adapters.train_o2_dp import ALL_NUMBERS, B1, compare, leaf_gaps  # noqa: F401
from benchmarks.adapters.train_o2_hybrid import load_gap
from benchmarks.reference import bailing_ref


class Trainer(train_o2_hybrid.Trainer):
    """``train_o2_dp.Trainer``'s feed and ``train_o2_hybrid.Trainer``'s step
    call around the ``bailing_hybrid`` decoder's step; the state is (masters,
    optimizer state, scaler, selection bias)."""

    def __init__(self, ctx, devices=None):  # noqa: D107 - builds its own step
        from apex_tpu import amp
        from apex_tpu.models import HybridDecoderConfig, HybridDecoderModel
        from apex_tpu.optimizers import fused_adam
        from apex_tpu.parallel import mesh as mesh_lib
        from apex_tpu.transformer.moe import router_bias_update

        self.ctx = ctx
        self.key = bailing_ref.seed_key(ctx["seed"])
        self.ref_dims = d = bailing_ref.dims(ctx["config"])
        self.engine = e = ctx["config"]["engine"]
        self.mix = ctx["mix"]["params"]
        self.seq = self.mix["seq"]
        # what the window's result carries as ``dims``: the model's own sizes
        # and the one latent layer as the accepted flash readers take it
        self.d = dict(d, **bailing_tree.attention_view(d))
        # the model before the mesh: a program that lacks the layer kind
        # refuses its settings here, before it has asked for anything
        self.model = model = HybridDecoderModel(HybridDecoderConfig(
            **bailing_tree.config_kwargs(d, remat=e["remat"])))
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
                        "bias_spread": jnp.max(bias, -1) - jnp.min(bias, -1),
                        "router_group_hit": jax.lax.pmean(aux["router_group_hit"], "dp"),
                        "kda_log_decay_min": jax.lax.pmin(aux["kda_log_decay_min"], "dp")}
            return master, opt_state, scaler, bias, loss, counters

        self.step = jax.jit(
            mesh_lib.shard_map(run, in_specs=(P(), P(), P(), P(), P("dp"), P("dp")),
                               out_specs=(P(), P(), P(), P(), P(), P())),
            donate_argnums=(0, 1, 2, 3))

        def weights(key):
            return bailing_tree.to_program(bailing_ref.make_weights(d, key))

        def init_state(key):
            master = amp.MasterWeights.create(weights(key), self.policy,
                                              keep_float32=model.float32_params)
            return (master, opt.init(master.master), amp.init_loss_scaler("dynamic"),
                    model.init_router_bias())

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
        self.dropped = 0
        self.bias_spread = []
        self.group_hit = []
        self.log_decay_min = []


def _take_counters(t):
    """(steps, expert layers, held) assignments of the steps since the
    counters were last taken; their dropped ones, the bias's spread, the
    group hits (per expert layer) and the smallest log decays are kept on
    ``t``."""
    got, t.counters = jax.device_get(t.counters), []
    t.dropped += int(sum(c["dropped"] for c in got))
    t.bias_spread += [c["bias_spread"] for c in got]
    t.group_hit += [c["router_group_hit"] for c in got]
    t.log_decay_min += [float(c["kda_log_decay_min"]) for c in got]
    return np.stack([c["expert_load"] for c in got])


def first_steps(t, ctx):
    """``train_o2_dp.first_steps``, the load counters of those steps and the
    bias they left."""
    t.counters, t.bias_spread, t.group_hit, t.log_decay_min = [], [], [], []
    train_o2_dp.first_steps(t, ctx)
    t.readings["expert_load"] = _take_counters(t)
    t.readings["router_bias"] = jax.device_get(t.state[3])


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
    run["router_group_hit"] = np.stack(t.group_hit)
    run["kda_log_decay_min"] = min(t.log_decay_min)
    run["train_flops_per_token"] = bailing_work.window_flops_per_token(run)
    run["expert_matmul_work"] = hybrid_work.window_expert_matmul_work(
        run, view=mla_work.expert_view)
    ctx["log"](f"window: selection bias spread at its end {run['bias_spread'][-1].max():.4g}; "
               f"{run['dropped']} local assignments dropped; kept groups held the held "
               f"experts' for {100 * run['router_group_hit'].mean():.1f} % of the tokens; "
               f"smallest per-step log decay {run['kda_log_decay_min']:.4f}")
    return run


def reference_readings(t, ctx, precision="float32"):
    """The same first steps through the plain reference, on one chip, the
    bias threaded through them. Only norms, projections, the load counters
    and the bias leave each step. A step is the reference's two halves in
    turn — ``grad_step``, then ``adam_update`` — with Adam's moments parked on
    the host in between: weights, moments and gradients at 16 B a parameter
    beside a row's activations are more than the chip holds at once."""
    d, steps = t.ref_dims, t.engine["check_steps"]
    first, count = d["experts_held"]

    def grads(w, bias, tokens, targets):
        loss, counts, g = bailing_ref.grad_step(w, bias, d, tokens, targets, precision=precision)
        p = bailing_tree.to_program(g)
        return loss, counts, g, {"norm": gpt_tree.leaf_norms(p),
                                 "projection": gpt_tree.leaf_projections(p)}

    def moved(w, key):
        return gpt_tree.leaf_norms(bailing_tree.to_program(jax.tree.map(
            lambda a, b: a - b, w, bailing_ref.make_weights(d, key))))

    out = {"loss": [], "expert_load": []}
    with jax.default_matmul_precision("highest"):
        w = jax.jit(lambda k: bailing_ref.make_weights(d, k))(t.key)
        opt = jax.device_get(jax.jit(bailing_ref.adam_init)(w))          # on the host
        bias = bailing_ref.bias_init(d)
        grads = jax.jit(grads)
        update = jax.jit(lambda w, opt, g: bailing_ref.adam_update(w, opt, g, lr=t.engine["lr"]),
                         donate_argnums=(0, 1))
        for i in range(steps):
            tokens, targets = t.host_batch(i)
            loss, counts, g, norms = grads(w, bias, jnp.asarray(tokens), jnp.asarray(targets))
            out["loss"].append(float(loss))
            out["expert_load"].append(np.asarray(counts[:, first:first + count]))
            if i == 0:
                out["first_gradient"] = jax.device_get(norms)
            del norms
            w, on_chip = update(w, jax.device_put(opt), g)
            opt = jax.device_get(on_chip)
            del g, on_chip
            bias = bailing_ref.bias_update(bias, counts, d)
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
    rows.append(("held_load_gap", load_gap(t.readings, ref), limits["held_load_gap"]))
    moves = bias_gap(t.readings, ref, t.ref_dims, t.engine["check_steps"])
    if "router_bias_gap" in limits:
        rows.append(("router_bias_gap", moves, limits["router_bias_gap"]))
    else:
        ctx["log"](f"reading (no limit in this configuration): router_bias_gap = {moves:.6g}")
    rows += compare(t.readings, ref, limits)
    from apex_tpu.parallel import mesh as mesh_lib
    mesh_lib.destroy_model_parallel()
    return rows
