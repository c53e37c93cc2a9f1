"""What the decoder families' test files and their kernels' share: how a result
is held to its reference, a seeded batch of tokens, and the kernels a traced
program calls. ``build`` stays with each family: it is the family."""
import collections

import jax
import jax.numpy as jnp
import numpy as np


def close(got, want, tol, name=""):
    """Every entry within ``tol`` of the reference's largest entry."""
    np.testing.assert_allclose(got, want, err_msg=name,
                               atol=tol * float(jnp.max(jnp.abs(want))) + 1e-9)


def gap(got, want):
    """The largest difference over the reference's largest entry."""
    return float(jnp.max(jnp.abs(got - want))) / (float(jnp.max(jnp.abs(want))) + 1e-30)


def batch(rows=2, seq=96, seed=0):
    """Tokens and targets below 256, drawn apart."""
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, 256, (rows, seq)), jnp.int32),
            jnp.asarray(rng.integers(0, 256, (rows, seq)), jnp.int32))


def kernel_calls(jaxpr):
    """Kernel name -> ``pallas_call`` equations, a call site at a time."""
    calls = collections.Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls[eqn.params["name"]] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            calls.update(kernel_calls(sub))
    return calls
