"""The grouped products of the dropless expert layer
(``ops/pallas/grouped_matmul.py``: ``moe_gmm`` / ``moe_gmm_dx`` /
``moe_gmm_dw``, interpreted) at the widths the cells give them: column blocks
that divide N, and fourteen and a half lane tiles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.pallas import grouped_matmul as gk
from apex_tpu.transformer import moe


@pytest.mark.parametrize("N", [768, 2816, 1024])
def test_grouped_dw_writes_every_column(N):
    """``moe_gmm_dw`` takes column blocks that divide N: at 2 x 1,408 = 2,816
    (= 5.5 x 512) and at 768 a 512-block left the last 256 columns of every
    expert's gradient unwritten, silently zero."""
    M, K, E = 4 * gk.TM, 128, 3
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K))
    dy = jax.random.normal(jax.random.PRNGKey(1), (M, N))
    tile_expert = jnp.array([0, 0, 2, 2], jnp.int32)
    n_used = jnp.array([3], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = gk.moe_gmm_dw(x, dy, tile_expert, n_used, E, interpret=True)
        per_tile = jnp.einsum("tmk,tmn->tkn", x.reshape(4, gk.TM, K)[:3],
                              dy.reshape(4, gk.TM, N)[:3])
    want = jax.ops.segment_sum(per_tile, tile_expert[:3], num_segments=E)
    np.testing.assert_allclose(got, want, atol=1e-3)
    assert float(jnp.min(jnp.max(jnp.abs(got[0]), axis=0))) > 0.1     # no column left at zero
    assert float(jnp.max(jnp.abs(got[1]))) == 0.0                     # an expert with no tile


@pytest.mark.parametrize("K,N", [(256, 1856), (1856, 256)])
def test_grouped_products_take_a_width_of_fourteen_and_a_half_lane_tiles(K, N):
    """1,856 = 14.5 x 128 as the output width and as the contracted one: all
    three ``moe_gmm*`` kernels at the width itself (whole-matrix blocks, no
    padding in HBM), no result column lost, none of ``dw`` left at zero."""
    M, E = 4 * gk.TM, 3
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    x, dy = jax.random.normal(k[0], (M, K)), jax.random.normal(k[1], (M, N))
    w = jax.random.normal(k[2], (E, K, N))
    tile_expert, n_used = jnp.array([0, 0, 2, 2], jnp.int32), jnp.array([3], jnp.int32)
    assert moe._gmm_shapes_ok(x, w) and moe._gmm_shapes_ok(dy, jnp.swapaxes(w, 1, 2))
    assert not moe._gmm_shapes_ok(x[:, :200], w[:, :200])
    used = (jnp.arange(4) < 3)[:, None, None]
    with jax.default_matmul_precision("highest"):
        out = gk.moe_gmm(x, w, tile_expert, n_used, interpret=True)
        dx = gk.moe_gmm_dx(dy, w, tile_expert, n_used, interpret=True)
        dw = gk.moe_gmm_dw(x, dy, tile_expert, n_used, E, interpret=True)
        xt, dyt = x.reshape(4, gk.TM, K), jnp.where(used, dy.reshape(4, gk.TM, N), 0)
        want = jnp.where(used, jnp.einsum("tmk,tkn->tmn", xt, w[tile_expert]), 0)
        want_dx = jnp.einsum("tmn,tkn->tmk", dyt, w[tile_expert])
        want_dw = jax.ops.segment_sum(jnp.einsum("tmk,tmn->tkn", xt, dyt), tile_expert,
                                      num_segments=E)
    assert out.shape == (M, N) and dx.shape == (M, K) and dw.shape == (E, K, N)
    np.testing.assert_allclose(out, want.reshape(M, N), atol=1e-3)
    np.testing.assert_allclose(dx, want_dx.reshape(M, K), atol=1e-3)
    np.testing.assert_allclose(dw, want_dw, atol=1e-3)
    assert float(jnp.min(jnp.max(jnp.abs(out[:gk.TM]), axis=0))) > 0.1   # every result column
    assert float(jnp.min(jnp.max(jnp.abs(dw[0]), axis=0))) > 0.1         # every column of dw
    assert float(jnp.max(jnp.abs(dw[1]))) == 0.0                         # an expert with no tile
