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


# the delta rules' kernels on a batch against the same kernels a row at a time: every batch at
# a short row; the cells' batch of two at every count of chunks — 3 an odd count, 6 short of
# a grid step, 8 a whole grid step, 16 two grid steps with the state and dS carried between
BATCH_AND_CHUNKS = [(1, 2), (2, 2), (3, 2), (4, 2), (2, 3), (2, 6), (2, 8), (2, 16)]


def batch_equals_its_rows(kernel_pair, args, do):
    """``kernel_pair(*args, do)`` (every result of a forward and a backward
    kernel, the batch leading) on the whole batch equals, bit for bit, the
    same call a row at a time: two rows a grid step (an even batch) and one
    (an odd batch, a batch of one) are the same arithmetic."""
    whole = kernel_pair(*args, do)
    for r in range(do.shape[0]):
        one = kernel_pair(*(a[r:r + 1] for a in args), do[r:r + 1])
        for x, y in zip(whole, one):
            np.testing.assert_array_equal(np.asarray(x[r:r + 1], np.float32),
                                          np.asarray(y, np.float32))


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
