"""What ``trinity-train-8k``'s limits were set from beyond what
``readings.py`` prints — on the chip, at the cell's own size, seed after seed:

* every number of the output check for the program and for the control (the
  plain reference in float8), ``held_load_gap`` and ``router_bias_gap`` among
  them, and what a selection bias left where it started would read;
* the first gradient's projection gap over the expert layers' tensors and
  over the rest, apart;
* for the first ``--pinned`` seeds, the first gradient of the program and of
  the control with every token's choice of experts pinned to the float32
  reference's (a per-token selection bias of ten on the chosen experts):
  what is left of the gaps once no near-tie of the top-k can fall another way.

    python benchmarks/tests/readings_afmoe.py --seeds 8 --pinned 4

The benchmark's own runs never run this, and it measures no window.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from benchmarks import run  # noqa: E402
from benchmarks.adapters import afmoe_tree, gpt_tree  # noqa: E402
from benchmarks.adapters import train_o2_afmoe as adapter  # noqa: E402
from benchmarks.reference import afmoe_ref  # noqa: E402

GRADIENT = {"first_gradient_norm_gap": 0, "first_gradient_projection_gap": 0}


def _readings(grads):
    return {"norm": gpt_tree.leaf_norms(grads), "projection": gpt_tree.leaf_projections(grads)}


def program_gradient(t):
    """The first gradient of the program's step (O2 model copy of the seed's
    weights, the scaler's first scale) under a given selection bias."""
    from apex_tpu import amp
    from apex_tpu.parallel import mesh as mesh_lib

    def grad(key, bias, tokens, targets):
        w = afmoe_tree.to_program(afmoe_ref.make_weights(t.ref_dims, key))
        master = amp.MasterWeights.create(w, t.policy)
        _, (grads, _, _) = amp.scaled_value_and_grad(
            lambda p, a, b: t.model.loss_fn(p, a, b, return_aux=True, router_bias=bias),
            has_aux=True)(amp.init_loss_scaler("dynamic"), master.model, tokens, targets)
        return _readings(jax.lax.pmean(grads, "dp"))

    return jax.jit(mesh_lib.shard_map(grad, in_specs=(P(), P(), P("dp"), P("dp")),
                                      out_specs=P()))


def reference_gradient(t, precision):
    d = t.ref_dims

    def grad(key, bias, tokens, targets):
        g = jax.grad(lambda w: afmoe_ref.loss(w, bias, d, tokens, targets,
                                              precision=precision)[0])(
            afmoe_ref.make_weights(d, key))
        return _readings(afmoe_tree.to_program(g))

    return jax.jit(grad)


def pins(t):
    """(Lm, tokens, E) float32: ten on the experts the float32 reference's
    tokens chose under the bias at rest, nothing elsewhere."""
    d = t.ref_dims

    def chosen(key, tokens):
        top_e = afmoe_ref.hidden(afmoe_ref.make_weights(d, key), afmoe_ref.bias_init(d), d,
                                 tokens, with_chosen=True)[2]
        return 10.0 * jnp.sum(jax.nn.one_hot(top_e, d["router_num_experts"],
                                             dtype=jnp.float32), axis=-2)

    return jax.jit(chosen)


def by_group(got, ref):
    """The projection gap's root mean square over the expert layers'
    tensors and over all the others."""
    gaps, labels = adapter.leaf_gaps(got["first_gradient"]["projection"],
                                     ref["first_gradient"]["projection"],
                                     scale=ref["first_gradient"]["norm"])
    moe = np.asarray(["['moe']" in n for n in labels])
    rms = lambda a: float(np.sqrt(np.mean(a ** 2)))  # noqa: E731
    return {"experts": rms(gaps[moe]), "rest": rms(gaps[~moe])}


def readings(t, ctx, seeds, pinned, base, say):
    """``say(seed, who, number, value)`` for every reading of ``seeds`` seeds
    from ``base`` on, the first ``pinned`` of them with the pinned routing
    too. ``t`` is the adapter's ``Trainer``."""
    steps = t.engine["check_steps"]
    refs = {}
    for i in range(seeds):
        ctx["seed"] = seed = base + 7919 * i
        adapter.first_steps(t, ctx)
        t.stop_feed()
        t.state = None
        refs[seed] = ref = adapter.reference_readings(t, ctx)
        who = {"program": t.readings,
               "control": adapter.reference_readings(t, ctx, precision="float8")}
        for name, got in who.items():
            for number, value, _ in adapter.compare(got, ref, adapter.ALL_NUMBERS):
                say(seed, name, number, value)
            say(seed, name, "held_load_gap", adapter.load_gap(got, ref))
            say(seed, name, "router_bias_gap", adapter.bias_gap(got, ref, t.ref_dims, steps))
            for group, value in by_group(got, ref).items():
                say(seed, name, f"first_gradient_projection_gap.{group}", value)
        rest = {"router_bias": np.zeros_like(ref["router_bias"])}
        say(seed, "bias_left_at_rest", "router_bias_gap",
            adapter.bias_gap(rest, ref, t.ref_dims, steps))
    # the pinned gradients last: three further programs, after every reading
    # that needs none of them
    gradients = {"program": program_gradient(t), "control": reference_gradient(t, "float8")}
    choose, inputs = pins(t), {}
    for seed in list(refs)[:pinned]:
        ctx["seed"], key = seed, afmoe_ref.seed_key(seed)
        tokens, targets = (jnp.asarray(b) for b in t.host_batch(0))
        inputs[seed] = (key, choose(key, tokens), tokens, targets)
    for name, grad in gradients.items():
        for seed, args in inputs.items():
            got = dict(refs[seed], first_gradient=jax.device_get(grad(*args)))
            for number, value, _ in adapter.compare(got, refs[seed], GRADIENT):
                say(seed, name + "_pinned", number, value)
            for group, value in by_group(got, refs[seed]).items():
                say(seed, name + "_pinned", f"first_gradient_projection_gap.{group}", value)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="trinity-train-8k")
    p.add_argument("--seeds", type=int, default=8)
    p.add_argument("--pinned", type=int, default=4)
    p.add_argument("--base", type=int, default=2_700_000_001)
    a = p.parse_args()
    manifest = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell, config = run.find_cell(manifest, a.workload)
    run.require_device(cell["chips"])
    run.keep_compile_cache()
    _, ctx = run.context(cell, config, a.base, 0.0)
    readings(adapter.Trainer(ctx), ctx, a.seeds, a.pinned, a.base,
             lambda seed, who, number, value: print(
                 f"seed {seed} {who} {number} = {value:.6g}", flush=True))


if __name__ == "__main__":
    main()
