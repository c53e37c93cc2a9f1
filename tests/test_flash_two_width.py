"""The seq-major flash kernels at two head widths and with a second score
term (latent attention's shared rotary key), in interpret mode against plain
``jnp``: forward, dq, dk, dv and the second term's dq2 and dk2 — the last
summed over ALL the q heads of its one key —, at more than one block each
way; the rule that sends shapes the one-pass backward cannot take to XLA."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import attention as ops
from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.pallas import attention as pk


def operands(b, s, h, h_kv, h2, d, dv, d2, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = lambda k, shape: jax.random.normal(k, shape, jnp.float32).astype(dtype)  # noqa: E731
    q, k, v = n(ks[0], (b, s, h, d)), n(ks[1], (b, s, h_kv, d)), n(ks[2], (b, s, h_kv, dv))
    second = (n(ks[3], (b, s, h, d2)), n(ks[4], (b, s, h2, d2))) if d2 else None
    return q, k, v, second, jax.random.normal(ks[5], (b, s, h, dv), jnp.float32)


def plain(q, k, v, second, scale, causal):
    """Materialised scores, every narrower head axis repeated, in float32."""
    h = q.shape[2]
    f = lambda x: jnp.repeat(x.astype(jnp.float32), h // x.shape[2], axis=2)  # noqa: E731
    s = jnp.einsum("bqhd,bkhd->bhqk", f(q), f(k))
    if second is not None:
        s = s + jnp.einsum("bqhd,bkhd->bhqk", f(second[0]), f(second[1]))
    s = s * scale
    if causal:
        n = s.shape[-1]
        s = jnp.where(jnp.arange(n)[None, :] <= jnp.arange(n)[:, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), f(v))


def both(args, causal, impl, scale=None):
    q, k, v, second, w = args

    def loss(q, k, v, second):
        o = flash_attention(q, k, v, causal=causal, layout="bshd", impl=impl, second=second,
                            scale=scale)
        return jnp.sum(o.astype(jnp.float32) * w), o

    def want(q, k, v, second):
        d2 = 0 if second is None else second[0].shape[-1]
        o = plain(q, k, v, second, scale or (q.shape[-1] + d2) ** -0.5, causal)
        return jnp.sum(o * w), o

    with jax.default_matmul_precision("highest"):
        (_, o), g = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True))(
            q, k, v, second)
        (_, o_ref), g_ref = jax.jit(jax.value_and_grad(
            want, argnums=(0, 1, 2, 3), has_aux=True))(q, k, v, second)
    return o, o_ref, jax.tree.leaves(g), jax.tree.leaves(g_ref)


CASES = {
    # DeepSeek-V2-Lite's form: heads of 128 + 64 rotary against values of 128, ONE rotary key
    "mla": dict(b=2, s=384, h=4, h_kv=4, h2=1, d=128, dv=128, d2=64),
    # 4 x 4 tiles: fully visible ones beside the skipped and the crossed
    "mla_four_blocks": dict(b=1, s=512, h=2, h_kv=2, h2=1, d=128, dv=128, d2=64),
    "mla_grouped": dict(b=1, s=256, h=8, h_kv=4, h2=2, d=128, dv=128, d2=64),
    "wider_values": dict(b=1, s=256, h=4, h_kv=2, h2=1, d=128, dv=256, d2=64),
    "narrower_values_no_second": dict(b=1, s=256, h=4, h_kv=2, h2=2, d=256, dv=128, d2=0),
}


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_match_plain_attention(case, causal, monkeypatch):
    """128-blocks: 2-3 blocks each way, so dq2 and dq add over kv blocks, dk,
    dv and dk2 over q blocks, heads and (dk2) the kv heads of a batch row."""
    monkeypatch.setattr(pk, "_fit_block", lambda n, pref: 128)
    o, o_ref, g, g_ref = both(operands(**CASES[case]), causal, "pallas")
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    names = ["dq", "dk", "dv", "dq2", "dk2"]
    for name, a, b in zip(names, g, g_ref):
        np.testing.assert_allclose(a, b, atol=3e-5 * float(jnp.max(jnp.abs(b))) + 1e-6,
                                   err_msg=name)
    if CASES[case]["d2"]:
        assert g[4].shape == (CASES[case]["b"], CASES[case]["s"], CASES[case]["h2"], 64)


def test_one_block_and_bf16_and_a_given_scale():
    args = operands(**dict(CASES["mla"], s=256), dtype=jnp.bfloat16, seed=3)
    o, o_ref, g, g_ref = both(args, True, "pallas", scale=0.1147)
    assert o.dtype == jnp.bfloat16 and o.shape == (2, 256, 4, 128)
    np.testing.assert_allclose(o.astype(jnp.float32), o_ref, atol=3e-2)
    for a, b in zip(g, g_ref):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(a.astype(jnp.float32), b.astype(jnp.float32),
                                   atol=4e-2 * float(jnp.max(jnp.abs(b.astype(jnp.float32)))))


def test_xla_composition_is_the_same_function():
    o, o_ref, g, g_ref = both(operands(**CASES["mla"]), True, "xla")
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(a, b, atol=3e-5 * float(jnp.max(jnp.abs(b))) + 1e-6)


def kernel_names(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += kernel_names(sub)
    return out


def traced_names(s, d=128, dv=128, d2=64, sk=None):
    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
    sk = sk or s

    def loss(q, k, v, q2, k2):
        return flash_attention(q, k, v, causal=True, layout="bshd", impl="auto",
                               second=(q2, k2)).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(
        bf16(1, s, 16, d), bf16(1, sk, 16, d), bf16(1, sk, 16, dv), bf16(1, s, 16, d2),
        bf16(1, sk, 1, d2))
    return kernel_names(jaxpr.jaxpr)


def test_shapes_the_one_pass_backward_cannot_take_go_to_xla(monkeypatch):
    monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")     # auto takes the kernels where it may
    mla = ["flash_fwd_bshd_mla", "flash_bwd_bshd_mla_fused"]
    assert traced_names(8192) == mla                         # the cell's call: 61.5 MiB
    assert pk._fused_bwd_vmem_bytes(8192, 128, 1024, 1024, 2, 128, 64) == int(61.5 * 2 ** 20)
    assert traced_names(16384) == mla
    assert traced_names(32768) == []                         # accumulators past the cap
    assert not pk.bshd_two_width_fits(32768, 32768, 128, 128, 64, 2)
    assert traced_names(1024, sk=2048) == []                 # two sequence lengths
    assert traced_names(1024, d2=32) == []                   # a rotary part under half a lane tile
    assert traced_names(1024, dv=192) == []                  # a value head that tiles no lanes
    # the plain call's rule is what it was: same bytes at one width, no second term
    assert (pk._fused_bwd_vmem_bytes(8192, 128, 1024, 1024, 2)
            == pk._fused_bwd_vmem_bytes(8192, 128, 1024, 1024, 2, 128, 0))


def test_what_a_two_width_call_refuses():
    q, k, v, second, _ = operands(**dict(CASES["mla"], s=128))
    with pytest.raises(ValueError, match="no bias, kv_lens, dropout or window"):
        flash_attention(q, k, v, causal=True, layout="bshd", second=second, window=8)
    with pytest.raises(ValueError, match="layout='bshd'"):
        flash_attention(q, k, v, causal=True, second=second)
    with pytest.raises(ValueError, match="second = "):
        flash_attention(q, k, v, causal=True, layout="bshd", second=(second[0], second[1][:, :64]))
    with pytest.raises(ValueError, match="no window"):
        pk.flash_fwd_bshd(q, k, v, scale=1.0, causal=True, interpret=True, window=8,
                          second=tuple(a.transpose(0, 2, 1, 3) for a in second))
    with pytest.raises(NotImplementedError, match="one-pass backward only"):
        lse = jnp.zeros((2, 4, 128, 8))
        wide = jnp.concatenate([k, k], 1)
        pk.flash_bwd_bshd(q, wide, jnp.concatenate([v, v], 1), v, lse, v, scale=1.0,
                          causal=True, interpret=True,
                          second=(second[0].transpose(0, 2, 1, 3),
                                  jnp.concatenate([second[1]] * 2, 1).transpose(0, 2, 1, 3)))
    assert ops._xla_two_width(q, k, v, None, None, 1.0, False).shape == v.shape[:2] + (4, 128)
