"""The toy that the expert layer's test files share (``test_moe_dropless.py``,
``test_moe_route.py``; ``test_expert_rows.py`` its widths): 16 experts of
128 x 128 top-4 with a shared expert, the reference's weights, the program's
packing of them, and the plain reference."""
import os
import sys

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import hybrid_ref as R  # noqa: E402

H, F, E, K = 128, 128, 16, 4
D = {"router_num_experts": E, "num_experts_per_tok": K, "norm_topk_prob": True,
     "experts_held": (0, E)}
SIG = {"router_num_experts": E, "num_experts_per_tok": K, "route_norm": True,
       "route_scale": 2.826, "experts_held": (0, E)}


def weights(seed=0, skew=None):
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 10))
    n = lambda *s: 0.05 * jax.random.normal(next(k), s)  # noqa: E731
    w = {"router": n(H, E), "w_gate": n(E, H, F), "w_up": n(E, H, F), "w_down": n(E, F, H),
         "shared_gate": n(H, F), "shared_up": n(H, F), "shared_down": n(F, H), "shared_mix": n(H)}
    if skew is not None:
        w["router"] = w["router"] + skew
    return w


def program(w, first=0, count=E):
    cut = lambda a: a[first:first + count]  # noqa: E731
    return {"router": w["router"],
            "w_gate_up": jnp.concatenate([cut(w["w_gate"]), cut(w["w_up"])], -1),
            "w_down": cut(w["w_down"]),
            "shared_gate_up": jnp.concatenate([w["shared_gate"], w["shared_up"]], -1),
            "shared_down": w["shared_down"], "shared_mix": w["shared_mix"]}


def reference(w, x, first=0, count=E):
    lw = dict(w, **{n: w[n][first:first + count] for n in ("w_gate", "w_up", "w_down")})
    y, aux, load = R.expert_layer(lw, D, x, "float32", held=(first, count))
    return y + R.shared_expert(w, x, "float32"), aux, load
