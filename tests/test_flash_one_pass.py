"""The one-pass flash backward (``flash_bwd_packed_fused``,
``flash_bwd_bshd_fused`` / ``flash_bwd_bshd_win_fused``), interpreted, against
the dq / dkv split and the gradients of the XLA composition: every group
size, kv_lens, dropout, the band, heads of 256 and the training dtype."""

import jax
import jax.numpy as jnp
import jax.random as jr
import numpy as np
import pytest

from apex_tpu.ops.attention import flash_attention

K = jr.PRNGKey(33)      # ``test_attention.py``'s, where these classes stood


class TestPackedOnePassBackward:
    """``flash_bwd_packed`` without a bias is one kernel at any number of
    blocks (``flash_bwd_packed_fused``): every score tile computed once,
    dk/dv summed over the kv group in fp32 VMEM and written at kv width.
    Checked against the gradients of the XLA composition (the same mask
    hash, so dropout agrees bit for bit)."""

    FULL, SHORT, DEAD, INSIDE = None, (512, 100), (0, 300), (512, 200)

    @pytest.mark.pallas
    @pytest.mark.parametrize(
        "group,h_kv,causal,lens,rate,s,block,dtype",
        [
            # more than one block (4 x 4 tiles of 128), every group size
            (1, 2, True, FULL, 0.0, 512, 128, jnp.float32),
            (4, 2, True, FULL, 0.0, 512, 128, jnp.float32),
            (16, 1, True, FULL, 0.0, 512, 128, jnp.float32),
            (1, 2, False, FULL, 0.0, 512, 128, jnp.float32),
            (4, 2, False, FULL, 0.0, 512, 128, jnp.float32),
            (16, 1, False, FULL, 0.0, 512, 128, jnp.float32),
            # kv_lens: one row shorter than a block, one of length 0
            (4, 1, True, SHORT, 0.0, 512, 128, jnp.float32),
            (4, 1, True, DEAD, 0.0, 512, 128, jnp.float32),
            (4, 1, False, SHORT, 0.0, 512, 128, jnp.float32),
            # a length that ends inside a tile the diagonal leaves fully
            # visible (200 of 512: tile (3, 1)): both bodies' mask branch
            (4, 1, True, INSIDE, 0.0, 512, 128, jnp.float32),
            # dropout, same seed as the forward; with lengths too
            (4, 1, True, FULL, 0.3, 512, 128, jnp.float32),
            (2, 2, False, FULL, 0.3, 512, 128, jnp.float32),
            (4, 1, True, DEAD, 0.3, 512, 128, jnp.float32),
            # the sequence is one block, whatever block was asked for
            (1, 2, True, FULL, 0.0, 128, 1024, jnp.float32),
            (4, 1, True, FULL, 0.3, 128, 1024, jnp.float32),
            (4, 1, False, (100, 128), 0.0, 128, 1024, jnp.float32),
            # the training dtype: grads in bf16, the group summed in fp32
            (16, 1, True, FULL, 0.0, 256, 128, jnp.bfloat16),
        ])
    def test_matches_xla_composition(self, group, h_kv, causal, lens, rate,
                                     s, block, dtype):
        from apex_tpu.ops.pallas import attention as pk

        b, d = 2, 32
        h = group * h_kv
        key = jr.fold_in(K, 2500 + group)
        qkv = jr.normal(key, (b, s, (h + 2 * h_kv) * d)).astype(dtype)
        do = jr.normal(jr.fold_in(key, 1), (b, s, h * d)).astype(dtype)
        kv_lens = None if lens is None else jnp.array(lens, jnp.int32)
        seed = jnp.int32(77) if rate else None
        scale = d ** -0.5
        kw = dict(scale=scale, causal=causal, kv_lens=kv_lens, bq=block,
                  bk=block, interpret=True, dropout_rate=rate,
                  dropout_seed=seed)

        def composition(q, k, v):
            return flash_attention(
                q, k, v, layout="bshd", impl="xla", causal=causal,
                kv_lens=kv_lens, scale=scale, dropout_rate=rate,
                dropout_seed=seed)

        f32 = qkv.astype(jnp.float32)
        q, k, v = (f32[..., :h * d].reshape(b, s, h, d),
                   f32[..., h * d:(h + h_kv) * d].reshape(b, s, h_kv, d),
                   f32[..., (h + h_kv) * d:].reshape(b, s, h_kv, d))
        with jax.default_matmul_precision("highest"):
            o, lse = pk.flash_fwd_packed(qkv, h, h_kv, d, full_lse=True, **kw)
            got = pk.flash_bwd_packed(qkv, h, h_kv, d, o, lse, do, **kw)
            want = jax.jit(lambda q, k, v, do: jax.vjp(composition, q, k, v)[1](do))(
                q, k, v, do.astype(jnp.float32).reshape(b, s, h, d))
        assert len(got) == 3
        tol = (dict(rtol=2e-4, atol=3e-5) if dtype == jnp.float32
               else dict(rtol=3e-2, atol=6e-2))
        for name, a, e, heads in zip(("dq", "dk", "dv"), got, want,
                                     (h, h_kv, h_kv)):
            assert a.shape == (b, s, heads * d) and a.dtype == dtype, name
            np.testing.assert_allclose(
                a.astype(jnp.float32), e.reshape(b, s, heads * d),
                err_msg=name, **tol)


class TestBshdOnePassBackward:
    """``flash_bwd_bshd`` without a bias at one sequence length is the packed
    layout's kernel over three arrays (``flash_bwd_bshd_fused``; on a window
    ``flash_bwd_bshd_win_fused``, its kv axis the band's run of blocks).
    Checked for dq, dk and dv against the dq/dkv split (the rule that picks
    the form given no VMEM to ask for) and against the gradients of the XLA
    composition (the same mask hash, so dropout agrees bit for bit)."""

    FULL, SHORT, DEAD, LATE, INSIDE = None, (512, 100), (0, 300), (512, 450), (512, 200)

    @pytest.mark.pallas
    @pytest.mark.parametrize(
        "group,h_kv,d,causal,window,lens,rate,s,block,dtype",
        [
            # 4 x 4 tiles of 128: groups of 1 and 8, causal and not
            (1, 2, 128, True, None, FULL, 0.0, 512, 128, jnp.float32),
            (8, 1, 128, True, None, FULL, 0.0, 512, 128, jnp.float32),
            (1, 2, 128, False, None, FULL, 0.0, 512, 128, jnp.float32),
            (8, 1, 128, False, None, FULL, 0.0, 512, 128, jnp.float32),
            # heads of 256, 2 x 2 tiles
            (8, 1, 256, True, None, FULL, 0.0, 256, 128, jnp.float32),
            (1, 2, 256, False, None, FULL, 0.0, 256, 128, jnp.float32),
            # the band: under a block, of a block, not a multiple of it, of
            # the sequence and beyond it
            (8, 1, 128, True, 5, FULL, 0.0, 512, 128, jnp.float32),
            (8, 1, 128, True, 128, FULL, 0.0, 512, 128, jnp.float32),
            (8, 1, 128, True, 200, FULL, 0.0, 512, 128, jnp.float32),
            (1, 2, 128, True, 300, FULL, 0.0, 512, 128, jnp.float32),
            (8, 1, 128, True, 512, FULL, 0.0, 512, 128, jnp.float32),
            (8, 1, 128, True, 4096, FULL, 0.0, 512, 128, jnp.float32),
            (8, 1, 256, True, 200, FULL, 0.0, 256, 128, jnp.float32),
            # 2 x 2 tiles of 256 at heads of 128
            (8, 1, 128, True, 300, FULL, 0.0, 512, 256, jnp.float32),
            # the sequence is one block, whatever block was asked for
            (1, 2, 128, True, None, FULL, 0.0, 128, 1024, jnp.float32),
            (8, 1, 128, True, 100, FULL, 0.0, 128, 1024, jnp.float32),
            (8, 1, 256, False, None, (100, 128), 0.0, 128, 1024, jnp.float32),
            # kv_lens short of a block edge, one row of length 0; under a
            # band a length that leaves every query a visible key
            (8, 1, 128, True, None, SHORT, 0.0, 512, 128, jnp.float32),
            (8, 1, 128, False, None, SHORT, 0.0, 512, 128, jnp.float32),
            (8, 1, 128, True, None, DEAD, 0.0, 512, 128, jnp.float32),
            (8, 1, 128, True, 200, LATE, 0.0, 512, 128, jnp.float32),
            # a length that ends inside a tile the diagonal (and the band)
            # leave fully visible
            (8, 1, 128, True, None, INSIDE, 0.0, 512, 128, jnp.float32),
            (8, 1, 128, True, 300, LATE, 0.0, 512, 128, jnp.float32),
            # dropout: the forward's mask regenerated from its seed
            (8, 1, 128, True, None, FULL, 0.3, 512, 128, jnp.float32),
            (2, 2, 128, False, None, FULL, 0.3, 512, 128, jnp.float32),
            (8, 1, 128, True, 200, FULL, 0.3, 512, 128, jnp.float32),
            (8, 1, 128, True, None, DEAD, 0.3, 512, 128, jnp.float32),
            # the training dtype: grads in bf16, the group summed in fp32
            (8, 1, 128, True, None, FULL, 0.0, 256, 128, jnp.bfloat16),
            (8, 1, 128, True, 200, FULL, 0.0, 256, 128, jnp.bfloat16),
            (8, 1, 256, True, None, FULL, 0.0, 256, 128, jnp.bfloat16),
        ])
    def test_matches_the_split_and_the_xla_composition(
            self, group, h_kv, d, causal, window, lens, rate, s, block,
            dtype, monkeypatch):
        from apex_tpu.ops.pallas import attention as pk

        b, h = 2, group * h_kv
        key = jr.fold_in(K, 3100 + group + d)
        q, k, v, do = (
            jr.normal(jr.fold_in(key, i), (b, s, heads, d)).astype(dtype)
            for i, heads in enumerate((h, h_kv, h_kv, h)))
        kv_lens = None if lens is None else jnp.array(lens, jnp.int32)
        seed = jnp.int32(77) if rate else None
        scale = d ** -0.5
        kw = dict(scale=scale, causal=causal, kv_lens=kv_lens, bq=block,
                  bk=block, interpret=True, dropout_rate=rate,
                  dropout_seed=seed, window=window)

        def composition(q, k, v):
            return flash_attention(
                q, k, v, layout="bshd", impl="xla", causal=causal,
                kv_lens=kv_lens, scale=scale, dropout_rate=rate,
                dropout_seed=seed, window=window)

        def names(*args):
            return str(jax.make_jaxpr(
                lambda *a: pk.flash_bwd_bshd(*a, **kw))(*args))

        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        with jax.default_matmul_precision("highest"):
            o, lse = pk.flash_fwd_bshd(q, k, v, full_lse=True, **kw)
            got = pk.flash_bwd_bshd(q, k, v, o, lse, do, **kw)
            one_pass = names(q, k, v, o, lse, do)
            monkeypatch.setattr(pk, "_VMEM_CAP", 0)
            split = pk.flash_bwd_bshd(q, k, v, o, lse, do, **kw)
            two_pass = names(q, k, v, o, lse, do)
            want = jax.jit(lambda q, k, v, do: jax.vjp(composition, q, k, v)[1](do))(
                f32(q), f32(k), f32(v), f32(do))
        fused_name = ("flash_bwd_bshd_fused" if window is None
                      else "flash_bwd_bshd_win_fused")
        assert fused_name in one_pass and "_dkv" not in one_pass
        assert "_dkv" in two_pass and "fused" not in two_pass
        assert len(got) == 3
        tol = (dict(rtol=2e-4, atol=3e-5) if dtype == jnp.float32
               else dict(rtol=3e-2, atol=6e-2))
        for name, a, sp, e, heads in zip(("dq", "dk", "dv"), got, split,
                                         want, (h, h_kv, h_kv)):
            assert a.shape == (b, s, heads, d) and a.dtype == dtype, name
            np.testing.assert_allclose(f32(a), f32(sp),
                                       err_msg=f"{name} vs split", **tol)
            np.testing.assert_allclose(f32(a), e, err_msg=f"{name} vs xla",
                                       **tol)
