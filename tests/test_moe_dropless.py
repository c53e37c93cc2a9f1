"""The dropless expert layer against the plain reference: uneven routing,
nothing dropped, the block-after-block path, and the share test — the
partial results of all shares, the shared expert counted once, add up to the
uncut layer's result."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu.transformer import moe  # noqa: E402
from benchmarks.reference import hybrid_ref as R  # noqa: E402

H, F, E, K = 128, 128, 16, 4
D = {"router_num_experts": E, "num_experts_per_tok": K, "norm_topk_prob": True,
     "experts_held": (0, E)}


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



@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("tokens", [(2, 96), (3, 100)])   # 192 rows a block, or 300 in 384
def test_uneven_routing_matches_the_reference_and_drops_nothing(impl, tokens):
    """All 16 experts held, so the routing fills top_k blocks and part of
    one more: the loop over blocks runs, forward and backward."""
    x = jax.random.normal(jax.random.PRNGKey(7), tokens + (H,))
    # positive features, so that a column of the router decides: expert 5
    # gets nearly every token, expert 3 none
    x = jnp.abs(x)
    skew = jnp.zeros((H, E)).at[:, 5].set(0.3).at[:, 3].set(-0.3)
    w = weights(skew=skew)
    flat = x.reshape(-1, H)
    with jax.default_matmul_precision("highest"):
        f = lambda p, x: moe.dropless_moe_layer(  # noqa: E731
            p, x, top_k=K, experts_held=(0, E), impl=impl)
        y, aux = f(program(w), x)
        want, want_aux, want_load = reference(w, flat)
        load = np.asarray(aux["expert_load"])
        assert load[3] == 0 and load[5] >= 0.9 * flat.shape[0] and load.sum() == K * flat.shape[0]
        assert int(aux["dropped"]) == 0
        np.testing.assert_array_equal(load, np.asarray(want_load))
        np.testing.assert_allclose(y.reshape(-1, H), want, atol=2e-5 * float(jnp.max(jnp.abs(want))))
        np.testing.assert_allclose(aux["load_balance_loss"], want_aux, rtol=1e-5)
        r = jax.random.normal(jax.random.PRNGKey(8), want.shape)
        loss = lambda p, x: jnp.sum(f(p, x)[0].reshape(-1, H) * r) + f(p, x)[1]["load_balance_loss"]  # noqa: E731
        ref_loss = lambda w, x: (lambda o: jnp.sum(o[0] * r) + o[1])(reference(w, x))  # noqa: E731
        gp, gx = jax.grad(loss, argnums=(0, 1))(program(w), x)
        gw, gxr = jax.grad(ref_loss, argnums=(0, 1))(w, flat)
    want_p = program(gw)
    for name in gp:
        np.testing.assert_allclose(gp[name], want_p[name], err_msg=name,
                                   atol=3e-5 * float(jnp.max(jnp.abs(want_p[name]))) + 1e-8)
    np.testing.assert_allclose(gx.reshape(-1, H), gxr, atol=3e-5 * float(jnp.max(jnp.abs(gxr))))


def test_shares_add_up_to_the_uncut_layer():
    """Four shares of four experts each: what every share computes alike
    (the shared expert) counted once, the parts add up to the whole layer —
    in the program and in the reference alike."""
    x = jax.random.normal(jax.random.PRNGKey(9), (192, H))
    w = weights(seed=1)
    with jax.default_matmul_precision("highest"):
        whole, _ = moe.dropless_moe_layer(program(w), x, top_k=K, impl="xla")
        shared = R.shared_expert(w, x, "float32")
        parts, ref_parts, loads = [], [], []
        for first in range(0, E, 4):
            y, aux = moe.dropless_moe_layer(program(w, first, 4), x, top_k=K,
                                            experts_held=(first, 4), impl="xla")
            parts.append(y - shared)
            ref_parts.append(reference(w, x, first, 4)[0] - shared)
            loads.append(np.asarray(aux["expert_load"]))
            assert int(aux["dropped"]) == 0
        uncut = reference(w, x)[0]
    scale = float(jnp.max(jnp.abs(uncut)))
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-5 * scale)
    np.testing.assert_allclose(sum(ref_parts) + shared, uncut, atol=1e-5 * scale)
    np.testing.assert_allclose(whole, uncut, atol=2e-5 * scale)
    assert np.concatenate(loads).sum() == K * x.shape[0]


def test_experts_held_must_match_the_matrices_given():
    w = weights()
    with pytest.raises(ValueError, match="experts_held"):
        moe.dropless_moe_layer(program(w, 0, 4), jnp.zeros((8, H)), top_k=K, experts_held=(0, 8))
    with pytest.raises(ValueError, match="experts_held"):
        moe.dropless_moe_layer(program(w, 0, 4), jnp.zeros((8, H)), top_k=K, experts_held=(14, 4))


def test_no_local_assignment_at_all_gives_the_shared_expert_alone():
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(11), (64, H)))
    w = weights(skew=jnp.zeros((H, E)).at[:, :4].set(-1.0))   # nobody picks experts 0-3
    for impl in ("xla", "pallas"):
        y, aux = moe.dropless_moe_layer(program(w, 0, 4), x, top_k=K, experts_held=(0, 4), impl=impl)
        assert int(aux["expert_load"].sum()) == 0
        np.testing.assert_allclose(y, R.shared_expert(w, x, "float32"), atol=1e-5)
        g = jax.grad(lambda p: jnp.sum(moe.dropless_moe_layer(
            p, x, top_k=K, experts_held=(0, 4), impl=impl)[0]))(program(w, 0, 4))
        assert float(jnp.max(jnp.abs(g["w_gate_up"]))) == 0.0
        assert float(jnp.max(jnp.abs(g["w_down"]))) == 0.0
