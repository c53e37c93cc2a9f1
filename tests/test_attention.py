"""Flash + ring attention tests.

Mirrors the reference's ``apex/contrib/test/fmha/test_fmha.py`` and
``multihead_attn`` tests: kernel vs dense-softmax reference, fwd and bwd —
plus ring attention (absent in the reference) against the same dense oracle.
"""

import jax
import jax.numpy as jnp
import jax.random as jr
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.ops.attention import (flash_attention, ring_attention,
                                    ulysses_attention, zigzag_shard,
                                    zigzag_unshard)
from apex_tpu.parallel import mesh as mesh_lib

K = jr.PRNGKey(33)

# On real TPU, fp32 matmuls go through the MXU with bf16-rounded operands at
# the default precision — both the kernels and the dense oracle carry
# ~1e-3-scale rounding the CPU (true-fp32) run doesn't, so the hardware run
# checks kernel-vs-oracle agreement at that scale, not fp32 exactness.
_EXACT = jax.default_backend() != "tpu"
ATOL = 2e-5 if _EXACT else 3e-3
RTOL = 2e-5 if _EXACT else 3e-3
G_ATOL = 2e-5 if _EXACT else 5e-3
G_RTOL = 2e-4 if _EXACT else 5e-3


def dense_ref(q, k, v, causal, scale=None):
    d = q.shape[-1]
    scale = scale or 1.0 / d ** 0.5
    s = jnp.einsum("...qd,...kd->...qk", q, k) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None] + (sk - sq)
        s = jnp.where(mask, s, -1e30)
    return jnp.einsum("...qk,...kd->...qd", jax.nn.softmax(s, -1), v)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        q = jr.normal(K, (2, 4, 64, 32))
        k = jr.normal(jr.fold_in(K, 1), (2, 4, 64, 32))
        v = jr.normal(jr.fold_in(K, 2), (2, 4, 64, 32))
        o = flash_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(o, dense_ref(q, k, v, causal), rtol=RTOL, atol=ATOL)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_dense(self, causal):
        q = jr.normal(K, (3, 32, 16))
        k = jr.normal(jr.fold_in(K, 3), (3, 32, 16))
        v = jr.normal(jr.fold_in(K, 4), (3, 32, 16))
        f1 = lambda q, k, v: jnp.sum(jnp.sin(flash_attention(q, k, v, causal=causal)))
        f2 = lambda q, k, v: jnp.sum(jnp.sin(dense_ref(q, k, v, causal)))
        g1 = jax.jit(jax.grad(f1, argnums=(0, 1, 2)))(q, k, v)
        g2 = jax.jit(jax.grad(f2, argnums=(0, 1, 2)))(q, k, v)
        for a, e in zip(g1, g2):
            np.testing.assert_allclose(a, e, rtol=G_RTOL, atol=G_ATOL)

    def test_long_sequence_beyond_reference_cap(self):
        # fmha caps at 512 and fused softmax at 2048; we run 4096
        q = jr.normal(K, (1, 4096, 16)) * 0.5
        o = flash_attention(q, q, q, causal=True)
        assert o.shape == (1, 4096, 16)
        assert bool(jnp.all(jnp.isfinite(o)))

    @pytest.mark.pallas
    @pytest.mark.parametrize("causal", [False, True])
    def test_pallas_kernel_fwd_bwd(self, causal, monkeypatch):
        # interpret mode checks the kernel's LOGIC, not hardware numerics —
        # force true-fp32 dots so the check is exact on TPU too (at default
        # precision the kernel's MXU dp and the elementwise delta disagree
        # by ~1e-3 exactly where the causal grad is identically zero)
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        q = jr.normal(K, (1, 256, 64)).astype(jnp.float32)
        k = jr.normal(jr.fold_in(K, 5), (1, 256, 64))
        v = jr.normal(jr.fold_in(K, 6), (1, 256, 64))
        with jax.default_matmul_precision("highest"):
            o = flash_attention(q, k, v, causal=causal, impl="pallas")
            np.testing.assert_allclose(o, dense_ref(q, k, v, causal),
                                       rtol=2e-5, atol=2e-5)
            f1 = lambda q, k, v: jnp.sum(jnp.cos(flash_attention(q, k, v, causal=causal, impl="pallas")))
            f2 = lambda q, k, v: jnp.sum(jnp.cos(dense_ref(q, k, v, causal)))
            g1 = jax.jit(jax.grad(f1, argnums=(0, 1, 2)))(q, k, v)
            g2 = jax.jit(jax.grad(f2, argnums=(0, 1, 2)))(q, k, v)
        for a, e in zip(g1, g2):
            np.testing.assert_allclose(a, e, rtol=2e-4, atol=2e-4)


class TestLseCarrierForms:
    """flash_bwd / flash_bwd_bshd accept lse as the sliced row vector OR
    the (…, LANES) lane carrier flash_fwd(full_lse=True) returns — both
    must produce identical grads (the custom-VJP residuals keep the
    carrier to skip a slice/re-broadcast pair per layer)."""

    @pytest.mark.pallas
    def test_sliced_vs_carrier_identical(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        from apex_tpu.ops.pallas import attention as A

        q = jr.normal(K, (2, 256, 64)).astype(jnp.float32)
        k = jr.normal(jr.fold_in(K, 41), (2, 256, 64))
        v = jr.normal(jr.fold_in(K, 42), (2, 256, 64))
        do = jr.normal(jr.fold_in(K, 43), (2, 256, 64))
        with jax.default_matmul_precision("highest"):
            o, lse = A.flash_fwd(q, k, v, scale=0.125, causal=True,
                                 interpret=True)
            o2, lse_c = A.flash_fwd(q, k, v, scale=0.125, causal=True,
                                    full_lse=True, interpret=True)
            np.testing.assert_array_equal(o, o2)
            np.testing.assert_array_equal(lse, lse_c[..., 0])
            g_sliced = A.flash_bwd(q, k, v, o, lse, do, scale=0.125,
                                   causal=True, interpret=True)
            g_carrier = A.flash_bwd(q, k, v, o, lse_c, do, scale=0.125,
                                    causal=True, interpret=True)
        for a, e in zip(g_carrier, g_sliced):
            np.testing.assert_array_equal(a, e)

    @pytest.mark.pallas
    def test_bshd_sliced_vs_carrier_identical(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        from apex_tpu.ops.pallas import attention as A

        q = jr.normal(K, (2, 256, 4, 16)).astype(jnp.float32)
        k = jr.normal(jr.fold_in(K, 44), (2, 256, 2, 16))
        v = jr.normal(jr.fold_in(K, 45), (2, 256, 2, 16))
        do = jr.normal(jr.fold_in(K, 46), (2, 256, 4, 16))
        with jax.default_matmul_precision("highest"):
            o, lse = A.flash_fwd_bshd(q, k, v, scale=0.25, causal=False,
                                      interpret=True)
            _, lse_c = A.flash_fwd_bshd(q, k, v, scale=0.25, causal=False,
                                        full_lse=True, interpret=True)
            np.testing.assert_array_equal(lse, lse_c[..., 0])
            g_sliced = A.flash_bwd_bshd(q, k, v, o, lse, do, scale=0.25,
                                        causal=False, interpret=True)
            g_carrier = A.flash_bwd_bshd(q, k, v, o, lse_c, do, scale=0.25,
                                         causal=False, interpret=True)
        for a, e in zip(g_carrier, g_sliced):
            np.testing.assert_array_equal(a, e)


class TestGroupedQueryAttention:
    """GQA/MQA: kv with fewer heads than q — beyond the reference's fmha
    (which requires equal head counts). Oracle: full MHA on repeated kv."""

    @pytest.mark.parametrize("kv_heads", [1, 2])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_repeated_kv(self, kv_heads, causal):
        b, hq, s, d = 2, 4, 32, 16
        q = jr.normal(K, (b, hq, s, d))
        k = jr.normal(jr.fold_in(K, 1), (b, kv_heads, s, d))
        v = jr.normal(jr.fold_in(K, 2), (b, kv_heads, s, d))
        o = flash_attention(q, k, v, causal=causal)
        rep = hq // kv_heads
        o_ref = dense_ref(q, jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1), causal)
        np.testing.assert_allclose(o, o_ref, rtol=RTOL, atol=ATOL)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_repeated_kv(self, causal):
        b, hq, kvh, s, d = 1, 4, 2, 32, 16
        q = jr.normal(K, (b, hq, s, d))
        k = jr.normal(jr.fold_in(K, 3), (b, kvh, s, d))
        v = jr.normal(jr.fold_in(K, 4), (b, kvh, s, d))
        rep = hq // kvh

        f1 = lambda q, k, v: jnp.sum(jnp.sin(flash_attention(q, k, v, causal=causal)))

        def f2(q, k, v):
            o = dense_ref(q, jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1), causal)
            return jnp.sum(jnp.sin(o))

        g1 = jax.jit(jax.grad(f1, argnums=(0, 1, 2)))(q, k, v)
        g2 = jax.jit(jax.grad(f2, argnums=(0, 1, 2)))(q, k, v)
        for a, e in zip(g1, g2):
            np.testing.assert_allclose(a, e, rtol=G_RTOL, atol=G_ATOL)

    @pytest.mark.pallas
    @pytest.mark.parametrize("causal", [False, True])
    def test_pallas_kernel_gqa_fwd_bwd(self, causal, monkeypatch):
        """The kernel's zero-copy kv index maps (fwd, dq, dkv) + the
        group-summed dk/dv epilogue."""
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        b, hq, kvh, s, d = 1, 4, 2, 256, 64
        q = jr.normal(K, (b, hq, s, d)).astype(jnp.float32)
        k = jr.normal(jr.fold_in(K, 5), (b, kvh, s, d))
        v = jr.normal(jr.fold_in(K, 6), (b, kvh, s, d))
        rep = hq // kvh
        with jax.default_matmul_precision("highest"):
            o = jax.jit(lambda q, k, v: flash_attention(
                q, k, v, causal=causal, impl="pallas"))(q, k, v)
            o_ref = dense_ref(q, jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1),
                              causal)
            np.testing.assert_allclose(o, o_ref, rtol=2e-5, atol=2e-5)
            f1 = lambda q, k, v: jnp.sum(jnp.cos(
                flash_attention(q, k, v, causal=causal, impl="pallas")))

            def f2(q, k, v):
                o = dense_ref(q, jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1),
                              causal)
                return jnp.sum(jnp.cos(o))

            g1 = jax.jit(jax.grad(f1, argnums=(0, 1, 2)))(q, k, v)
            g2 = jax.jit(jax.grad(f2, argnums=(0, 1, 2)))(q, k, v)
        for a, e in zip(g1, g2):
            np.testing.assert_allclose(a, e, rtol=2e-4, atol=2e-4)

    @pytest.mark.pallas
    def test_bf16_gqa_dkv_accumulates_fp32(self, monkeypatch):
        """ADVICE r2: the dkv kernel's per-q-head partials must be fp32 so
        the group sum doesn't round each head's contribution to bf16 first.
        With fp32 partials, bf16-input dk differs from the fp32 oracle by
        one output rounding, not by group-many."""
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        b, hq, kvh, s, d = 1, 8, 1, 128, 64  # MQA: group of 8 partials
        q32 = jr.normal(K, (b, hq, s, d))
        k32 = jr.normal(jr.fold_in(K, 7), (b, kvh, s, d))
        v32 = jr.normal(jr.fold_in(K, 8), (b, kvh, s, d))
        to16 = lambda x: x.astype(jnp.bfloat16)

        def loss(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, impl="pallas").astype(jnp.float32))

        with jax.default_matmul_precision("highest"):
            _, dk16, _ = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(
                to16(q32), to16(k32), to16(v32))
            _, dk32, _ = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(
                q32, k32, v32)
        err = jnp.max(jnp.abs(dk16.astype(jnp.float32) - dk32))
        # one bf16 rounding of the final sum: |err| <= ~2^-8 * |dk|;
        # bf16-rounded partials would accumulate ~sqrt(8) times that
        bound = float(jnp.max(jnp.abs(dk32))) * 2 ** -8
        assert float(err) <= bound * 1.5, (float(err), bound)

    @pytest.mark.pallas
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("h,kv_heads,d", [(4, 4, 128), (4, 2, 128),
                                              (4, 1, 128), (1, 1, 64)])
    def test_bshd_layout_kernels_match_dense(self, causal, h, kv_heads, d,
                                             monkeypatch):
        """Seq-major (b, s, h, d) kernels — the zero-layout-copy path the
        flagship uses — fwd + grads against the dense oracle, incl. GQA.
        Shapes restricted to the folded-layout tiling rule: d must tile
        128 lanes itself (d=64 only single-head) — see bshd_kernel_ok."""
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        b, s = 2, 256
        q = jr.normal(K, (b, s, h, d))
        k = jr.normal(jr.fold_in(K, 13), (b, s, kv_heads, d))
        v = jr.normal(jr.fold_in(K, 14), (b, s, kv_heads, d))
        rep = h // kv_heads

        def dense(q, k, v):
            # oracle in (b, h, s, d) with repeated kv
            t = lambda x: x.transpose(0, 2, 1, 3)
            return t(dense_ref(t(q), jnp.repeat(t(k), rep, 1),
                               jnp.repeat(t(v), rep, 1), causal))

        with jax.default_matmul_precision("highest"):
            o = flash_attention(q, k, v, causal=causal, layout="bshd",
                                impl="pallas")
            np.testing.assert_allclose(o, dense(q, k, v), rtol=2e-5,
                                       atol=2e-5)

            f1 = lambda q, k, v: jnp.sum(jnp.cos(flash_attention(
                q, k, v, causal=causal, layout="bshd", impl="pallas")))
            f2 = lambda q, k, v: jnp.sum(jnp.cos(dense(q, k, v)))
            g1 = jax.jit(jax.grad(f1, argnums=(0, 1, 2)))(q, k, v)
            g2 = jax.jit(jax.grad(f2, argnums=(0, 1, 2)))(q, k, v)
        for a, e in zip(g1, g2):
            np.testing.assert_allclose(a, e, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_bshd_xla_fallback_matches_dense(self, causal):
        """Below the crossover the bshd entry runs the XLA composition."""
        b, h, s, d = 2, 4, 32, 16
        q = jr.normal(K, (b, s, h, d))
        k = jr.normal(jr.fold_in(K, 15), (b, s, 2, d))
        v = jr.normal(jr.fold_in(K, 16), (b, s, 2, d))
        t = lambda x: x.transpose(0, 2, 1, 3)
        o = flash_attention(q, k, v, causal=causal, layout="bshd")
        ref = t(dense_ref(t(q), jnp.repeat(t(k), 2, 1),
                          jnp.repeat(t(v), 2, 1), causal))
        np.testing.assert_allclose(o, ref, rtol=RTOL, atol=ATOL)
        g = jax.jit(jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, causal=causal, layout="bshd") ** 2)))(q)
        gref = jax.jit(jax.grad(lambda q: jnp.sum(t(dense_ref(
            t(q), jnp.repeat(t(k), 2, 1), jnp.repeat(t(v), 2, 1),
            causal)) ** 2)))(q)
        np.testing.assert_allclose(g, gref, rtol=G_RTOL, atol=G_ATOL)

    def test_bshd_rejects_bad_lens_shape_and_bad_rank(self):
        q = jr.normal(K, (2, 32, 4, 16))
        # bshd kv_lens are per-BATCH (b,) — per-(b, h) is the bhsd form
        with pytest.raises(ValueError, match="per-batch kv_lens"):
            flash_attention(q, q, q, layout="bshd",
                            kv_lens=jnp.ones((2, 4), jnp.int32))
        with pytest.raises(ValueError, match="bshd"):
            flash_attention(q.reshape(8, 32, 16), q.reshape(8, 32, 16),
                            q.reshape(8, 32, 16), layout="bshd")

    def test_bshd_eligibility_rule(self):
        """The folded layout's d-wide blocks must tile 128 lanes — d=64
        multi-head configs are NOT kernel-eligible (would fail Mosaic's
        trailing-tile rule on hardware; caught by review r3)."""
        from apex_tpu.ops.attention import bshd_kernel_ok

        assert bshd_kernel_ok(1024, 1024, 8, 128, jnp.bfloat16)
        assert bshd_kernel_ok(1024, 1024, 1, 64, jnp.bfloat16)
        assert not bshd_kernel_ok(1024, 1024, 8, 64, jnp.bfloat16)
        assert not bshd_kernel_ok(1000, 1024, 8, 128, jnp.bfloat16)
        assert not bshd_kernel_ok(1024, 1024, 8, 128, jnp.float16)
        # d=64 multi-head with explicit pallas raises rather than lowering
        q = jr.normal(K, (2, 256, 4, 64))
        with pytest.raises(ValueError, match="tiling"):
            flash_attention(q, q, q, layout="bshd", impl="pallas")

    @pytest.mark.pallas
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("kv_heads, d", [(4, 16), (2, 16), (4, 64)])
    def test_fused_qkv_attention_matches_composition(self, kv_heads, d, causal,
                                                     monkeypatch):
        """The flagship's zero-layout-copy block (packed projection →
        window-reading kernels → output GEMM, hand-written VJP): forward
        and EVERY cotangent (x, packed weight, packed bias, out weight)
        against the composed einsum+dense formulation. Four heads of 64 ride
        the pair kernels, two heads to a 128-lane block, at blocks of 128 of
        256 positions: causal, the four tiles of a pair are one skipped, one
        fully visible and two crossed by the diagonal."""
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        from apex_tpu.ops.attention import fused_qkv_attention

        b, s, H, h = 2, 256, 64, 4
        hkv = kv_heads
        G = h + 2 * hkv
        key = jr.fold_in(K, 31)
        x = jr.normal(key, (b, s, H))
        w_qkv = jr.normal(jr.fold_in(key, 1), (G * d, H)) * 0.1
        b_qkv = jr.normal(jr.fold_in(key, 2), (G * d,)) * 0.1
        w_out = jr.normal(jr.fold_in(key, 3), (H, h * d)) * 0.1
        scale = 1.0 / d ** 0.5

        def composed(x, w_qkv, b_qkv, w_out):
            qkv = jnp.einsum("bsH,FH->bsF", x, w_qkv) + b_qkv
            qkv = qkv.reshape(b, s, G, d)
            t = lambda z: z.transpose(0, 2, 1, 3)
            q, k, v = (t(qkv[:, :, :h]), t(qkv[:, :, h:h + hkv]),
                       t(qkv[:, :, h + hkv:]))
            rep = h // hkv
            o = dense_ref(q, jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1),
                          causal, scale)
            return jnp.einsum("bhsd,Hhd->bsH", o,
                              w_out.reshape(H, h, d))

        def fused(x, w_qkv, b_qkv, w_out):
            return fused_qkv_attention(x, w_qkv, b_qkv, w_out, None, None,
                                       None, h, hkv, d, scale, causal)

        with jax.default_matmul_precision("highest"):
            y1 = fused(x, w_qkv, b_qkv, w_out)
            y2 = composed(x, w_qkv, b_qkv, w_out)
            np.testing.assert_allclose(y1, y2, rtol=2e-5, atol=2e-5)

            loss1 = lambda *a: jnp.sum(jnp.sin(fused(*a)))
            loss2 = lambda *a: jnp.sum(jnp.sin(composed(*a)))
            g1 = jax.jit(jax.grad(loss1, argnums=(0, 1, 2, 3)))(
                x, w_qkv, b_qkv, w_out)
            g2 = jax.jit(jax.grad(loss2, argnums=(0, 1, 2, 3)))(
                x, w_qkv, b_qkv, w_out)
        for a, e, name in zip(g1, g2, ("dx", "dw_qkv", "db_qkv", "dw_out")):
            np.testing.assert_allclose(a, e, rtol=3e-4, atol=3e-4,
                                       err_msg=name)
        names = str(jax.make_jaxpr(jax.grad(loss1))(x, w_qkv, b_qkv, w_out))
        assert ("flash_bwd_packed_pair_fused" in names) == (d == 64)

    def test_packed_eligibility_rule(self):
        """``fused_qkv_attention``'s gate: the folded rule, or heads of 64 in
        pairs — an even number of them, no grouped kv, a sequence of whole
        128-blocks that fits the one-pass backward, never float16."""
        from apex_tpu.ops.attention import packed_kernel_ok
        from apex_tpu.ops.pallas import attention as pk

        assert packed_kernel_ok(1024, 16, 16, 64, jnp.bfloat16)
        assert packed_kernel_ok(1024, 16, 16, 64, jnp.float32)
        assert not packed_kernel_ok(1024, 15, 15, 64, jnp.bfloat16)    # an odd count (tp)
        assert not packed_kernel_ok(1024, 16, 8, 64, jnp.bfloat16)     # grouped kv
        assert not packed_kernel_ok(1024, 16, 16, 64, jnp.float16)
        assert not packed_kernel_ok(1000, 16, 16, 64, jnp.bfloat16)
        assert not packed_kernel_ok(65536, 16, 16, 64, jnp.bfloat16)   # past the accumulators
        assert not packed_kernel_ok(1024, 16, 16, 32, jnp.bfloat16)
        # heads of 128 and the single head of 64: as bshd_kernel_ok has them
        assert packed_kernel_ok(8192, 16, 2, 128, jnp.bfloat16)
        assert packed_kernel_ok(1024, 1, 1, 64, jnp.bfloat16)
        assert not packed_kernel_ok(1024, 8, 8, 128, jnp.float16)
        assert not packed_kernel_ok(1000, 8, 8, 128, jnp.bfloat16)
        # the blocks of a pair call, from the sequence length alone
        assert [pk._pair_block(s) for s in (128, 256, 1024, 2048, 8192)] == [
            128, 128, 512, 1024, 1024]
        with pytest.raises(ValueError, match="no score bias"):
            pk.flash_fwd_packed(jnp.zeros((1, 128, 6 * 64)), 2, 2, 64, scale=1.0,
                                causal=True, bias=jnp.zeros((1, 128, 128)))

    def test_causal_sq_gt_sk_raises(self):
        """ADVICE r2: bottom-right causal with sq > sk has rows attending
        nothing — reject instead of emitting exp(0) garbage."""
        q = jr.normal(K, (2, 64, 16))
        k = jr.normal(jr.fold_in(K, 9), (2, 32, 16))
        with pytest.raises(ValueError, match="sq <= sk"):
            flash_attention(q, k, k, causal=True)

    def test_mismatched_heads_raise(self):
        q = jr.normal(K, (2, 3, 32, 16))
        k = jr.normal(K, (2, 2, 32, 16))
        with pytest.raises(ValueError, match="kv heads"):
            flash_attention(q, k, k)
        # a mismatched BATCH dim must not be mistaken for a kv-head group
        q = jr.normal(K, (2, 4, 32, 16))
        k = jr.normal(K, (1, 4, 32, 16))
        with pytest.raises(ValueError, match="equal batch dims"):
            flash_attention(q, k, k)


class TestVarlenAttention:
    """Per-row kv valid lengths (padded batches) — the flash analog of the
    reference's mask-tensor softmax, expressed in O(rows)."""

    def _oracle(self, q, k, v, lens, causal):
        sk = k.shape[-2]
        s = jnp.einsum("...qd,...kd->...qk", q, k) / q.shape[-1] ** 0.5
        if causal:
            sq = s.shape[-2]
            cm = jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None] + (sk - sq)
            s = jnp.where(cm, s, -1e30)
        lm = jnp.arange(sk)[None, None, :] < lens[:, None, None]
        s = jnp.where(lm, s, -1e30)
        o = jnp.einsum("...qk,...kd->...qd", jax.nn.softmax(s, -1), v)
        return jnp.where((lens == 0)[:, None, None], 0.0, o)

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_masked_dense(self, causal):
        bh, s, d = 4, 32, 16
        q = jr.normal(K, (bh, s, d))
        k = jr.normal(jr.fold_in(K, 1), (bh, s, d))
        v = jr.normal(jr.fold_in(K, 2), (bh, s, d))
        lens = jnp.array([32, 17, 1, 0], jnp.int32)
        o = flash_attention(q, k, v, causal=causal, kv_lens=lens)
        np.testing.assert_allclose(o, self._oracle(q, k, v, lens, causal),
                                   rtol=RTOL, atol=ATOL)

    def test_grads_match_masked_dense(self):
        bh, s, d = 3, 32, 16
        q = jr.normal(K, (bh, s, d))
        k = jr.normal(jr.fold_in(K, 3), (bh, s, d))
        v = jr.normal(jr.fold_in(K, 4), (bh, s, d))
        lens = jnp.array([32, 9, 0], jnp.int32)
        f1 = lambda q, k, v: jnp.sum(jnp.sin(
            flash_attention(q, k, v, causal=True, kv_lens=lens)))
        f2 = lambda q, k, v: jnp.sum(jnp.sin(self._oracle(q, k, v, lens, True)))
        g1 = jax.jit(jax.grad(f1, argnums=(0, 1, 2)))(q, k, v)
        g2 = jax.jit(jax.grad(f2, argnums=(0, 1, 2)))(q, k, v)
        for a, e in zip(g1, g2):
            np.testing.assert_allclose(a, e, rtol=G_RTOL, atol=G_ATOL)

    @pytest.mark.pallas
    def test_pallas_kernel_varlen_fwd_bwd(self, monkeypatch):
        """In-kernel masking + dynamic block skip + dead-row lse pinning."""
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        bh, s, d = 2, 256, 64
        q = jr.normal(K, (bh, s, d)).astype(jnp.float32)
        k = jr.normal(jr.fold_in(K, 5), (bh, s, d))
        v = jr.normal(jr.fold_in(K, 6), (bh, s, d))
        lens = jnp.array([256, 0], jnp.int32)  # include a DEAD row: the
        # kernel's all-blocks-skipped path + lse pinning must hold in-kernel
        with jax.default_matmul_precision("highest"):
            o = flash_attention(q, k, v, causal=True, kv_lens=lens,
                                impl="pallas")
            np.testing.assert_allclose(o, self._oracle(q, k, v, lens, True),
                                       rtol=2e-5, atol=2e-5)
            f1 = lambda q, k, v: jnp.sum(jnp.cos(flash_attention(
                q, k, v, causal=True, kv_lens=lens, impl="pallas")))
            f2 = lambda q, k, v: jnp.sum(jnp.cos(
                self._oracle(q, k, v, lens, True)))
            g1 = jax.jit(jax.grad(f1, argnums=(0, 1, 2)))(q, k, v)
            g2 = jax.jit(jax.grad(f2, argnums=(0, 1, 2)))(q, k, v)
        for a, e in zip(g1, g2):
            np.testing.assert_allclose(a, e, rtol=2e-4, atol=2e-4)

    def test_varlen_with_gqa(self):
        b, hq, kvh, s, d = 2, 4, 2, 32, 16
        q = jr.normal(K, (b, hq, s, d))
        k = jr.normal(jr.fold_in(K, 7), (b, kvh, s, d))
        v = jr.normal(jr.fold_in(K, 8), (b, kvh, s, d))
        lens = jnp.broadcast_to(jnp.array([20, 32], jnp.int32)[:, None],
                                (b, hq))
        o = flash_attention(q, k, v, kv_lens=lens)
        rep = hq // kvh
        kr, vr = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
        ref = self._oracle(q.reshape(b * hq, s, d), kr.reshape(b * hq, s, d),
                           vr.reshape(b * hq, s, d), lens.reshape(-1),
                           False).reshape(b, hq, s, d)
        np.testing.assert_allclose(o, ref, rtol=RTOL, atol=ATOL)

    def test_bad_lens_shape_raises(self):
        q = jr.normal(K, (2, 4, 32, 16))
        with pytest.raises(ValueError, match="kv_lens"):
            flash_attention(q, q, q, kv_lens=jnp.ones((2,), jnp.int32))


def _ring_apply(mesh, cp, causal, q, k, v):
    """Run ring attention on globally-laid-out q/k/v: zigzag-permute for
    causal (the required layout), shard, un-permute the output."""
    if causal:
        q, k, v = (zigzag_shard(x, cp, 1) for x in (q, k, v))
    o = mesh_lib.shard_map(
        lambda q, k, v: ring_attention(q, k, v, causal=causal),
        mesh=mesh,
        in_specs=(P(None, "cp"),) * 3,
        out_specs=P(None, "cp"),
    )(q, k, v)
    return zigzag_unshard(o, cp, 1) if causal else o


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense_full_sequence(self, causal):
        cp = 4
        mesh = mesh_lib.make_mesh(context_parallel_size=cp)
        S = 32  # full sequence; each device holds 8
        q = jr.normal(K, (2, S, 16))
        k = jr.normal(jr.fold_in(K, 7), (2, S, 16))
        v = jr.normal(jr.fold_in(K, 8), (2, S, 16))

        o = _ring_apply(mesh, cp, causal, q, k, v)
        np.testing.assert_allclose(
            o, dense_ref(q, k, v, causal), rtol=RTOL, atol=ATOL
        )

    @pytest.mark.parametrize("causal", [False, True])
    def test_grouped_kv_matches_dense(self, causal):
        """GQA under context parallelism: the NARROW kv rotates the ring
        (the bandwidth win); result == dense on repeated kv."""
        cp = 4
        mesh = mesh_lib.make_mesh(context_parallel_size=cp)
        S, hq, kvh, d = 32, 4, 2, 16
        q = jr.normal(K, (hq, S, d))         # (bh_q, s, d) rows
        k = jr.normal(jr.fold_in(K, 7), (kvh, S, d))
        v = jr.normal(jr.fold_in(K, 8), (kvh, S, d))

        o = _ring_apply(mesh, cp, causal, q, k, v)
        rep = hq // kvh
        np.testing.assert_allclose(
            o, dense_ref(q, jnp.repeat(k, rep, 0), jnp.repeat(v, rep, 0),
                         causal),
            rtol=RTOL, atol=ATOL)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_dense(self, causal):
        """Full q/k/v gradient parity against the dense oracle — exercises
        the distributed flash backward (traveling dkv accumulators)."""
        cp = 4
        mesh = mesh_lib.make_mesh(context_parallel_size=cp)
        S = 32
        q = jr.normal(K, (2, S, 16))
        k = jr.normal(jr.fold_in(K, 9), (2, S, 16))
        v = jr.normal(jr.fold_in(K, 10), (2, S, 16))

        def local_loss(q, k, v):
            # local shard's loss term; the global loss is the implicit sum
            # over shards, and the ring's reverse permutes deliver each
            # shard's cotangent contributions (psum here would double-count
            # under the conservative collective transpose)
            o = ring_attention(q, k, v, causal=causal)
            return jnp.sum(o * o)

        qs, ks, vs = ((zigzag_shard(x, cp, 1) for x in (q, k, v))
                      if causal else (q, k, v))
        g = jax.jit(mesh_lib.shard_map(
            lambda q, k, v: jax.grad(local_loss, argnums=(0, 1, 2))(q, k, v),
            mesh=mesh,
            in_specs=(P(None, "cp"),) * 3,
            out_specs=(P(None, "cp"),) * 3,
        ))(qs, ks, vs)
        if causal:
            g = tuple(zigzag_unshard(x, cp, 1) for x in g)
        gref = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(dense_ref(q, k, v, causal) ** 2),
            argnums=(0, 1, 2),
        ))(q, k, v)
        for a, e in zip(g, gref):
            np.testing.assert_allclose(a, e, rtol=G_RTOL, atol=G_ATOL)

    def test_grouped_kv_grads_match_dense(self):
        """GQA causal grads through the ring (narrow dkv travels the ring,
        group-summed by the kernel backward)."""
        cp = 4
        mesh = mesh_lib.make_mesh(context_parallel_size=cp)
        S, hq, kvh, d = 32, 4, 2, 16
        q = jr.normal(K, (hq, S, d))
        k = jr.normal(jr.fold_in(K, 11), (kvh, S, d))
        v = jr.normal(jr.fold_in(K, 12), (kvh, S, d))

        def local_loss(q, k, v):
            return jnp.sum(ring_attention(q, k, v, causal=True) ** 2)

        qs, ks, vs = (zigzag_shard(x, cp, 1) for x in (q, k, v))
        g = jax.jit(mesh_lib.shard_map(
            lambda q, k, v: jax.grad(local_loss, argnums=(0, 1, 2))(q, k, v),
            mesh=mesh,
            in_specs=(P(None, "cp"),) * 3,
            out_specs=(P(None, "cp"),) * 3,
        ))(qs, ks, vs)
        g = tuple(zigzag_unshard(x, cp, 1) for x in g)
        rep = hq // kvh

        def dense_loss(q, k, v):
            return jnp.sum(dense_ref(q, jnp.repeat(k, rep, 0),
                                     jnp.repeat(v, rep, 0), True) ** 2)

        gref = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))(q, k, v)
        for a, e in zip(g, gref):
            np.testing.assert_allclose(a, e, rtol=G_RTOL, atol=G_ATOL)

    def test_zigzag_roundtrip(self):
        x = jr.normal(K, (3, 48, 4))
        for cp in (2, 3, 4):
            rt = zigzag_unshard(zigzag_shard(x, cp, 1), cp, 1)
            np.testing.assert_array_equal(rt, x)
        with pytest.raises(ValueError, match="stripes"):
            zigzag_shard(x, 5, 1)

    def test_causal_flops_are_lower_triangle_only(self):
        """The zigzag schedule's whole point: per ring step every rank does
        exactly TWO stripe-sized (ss) attention pieces — no full-shard
        matmuls, no masked-and-discarded work — and the only 2ss-sized dots
        are the single local diagonal. Verified on the compiled HLO's dot
        inventory (the scan body appears once)."""
        import re
        from collections import Counter

        cp = 4
        mesh = mesh_lib.make_mesh(context_parallel_size=cp)
        S, d = 512, 256
        ss = S // cp // 2  # stripe length
        q = jr.normal(K, (2, S, d))

        fn = mesh_lib.shard_map(
            lambda q, k, v: ring_attention(q, k, v, causal=True),
            mesh=mesh, in_specs=(P(None, "cp"),) * 3,
            out_specs=P(None, "cp"),
        )
        txt = jax.jit(fn).lower(q, q, q).compile().as_text()
        dots = Counter(
            m.group(1) for m in re.finditer(r"= (\S+) dot\(", txt))
        # scan body (runs cp-1 times): piece1 + piece2 = 2 QK dots (ss, ss)
        # and 2 PV dots (ss, d)
        assert dots.get(f"f32[2,{ss},{ss}]{{2,1,0}}") == 2, dots
        assert dots.get(f"f32[2,{ss},{d}]{{2,1,0}}") == 2, dots
        # the local diagonal: exactly one 2ss-sized QK + PV pair, nothing
        # bigger anywhere
        assert dots.get(f"f32[2,{2*ss},{2*ss}]{{2,1,0}}") == 1, dots
        assert dots.get(f"f32[2,{2*ss},{d}]{{2,1,0}}") == 1, dots
        assert sum(dots.values()) == 6, dots


class TestUlyssesAttention:
    """All-to-all sequence parallelism (SURVEY §2.3's absent Ulysses row)
    against the same dense oracle as flash/ring."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense_full_sequence(self, causal):
        sp = 4
        mesh = mesh_lib.make_mesh(context_parallel_size=sp)
        B, S, H, D = 2, 32, 8, 16
        q = jr.normal(K, (B, S, H, D))
        k = jr.normal(jr.fold_in(K, 21), (B, S, H, D))
        v = jr.normal(jr.fold_in(K, 22), (B, S, H, D))

        o = mesh_lib.shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, causal=causal),
            mesh=mesh,
            in_specs=(P(None, "cp"),) * 3,
            out_specs=P(None, "cp"),
        )(q, k, v)
        # oracle: per-head dense attention over the full sequence
        ref = dense_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(o, ref, rtol=RTOL, atol=ATOL)

    @pytest.mark.pallas
    def test_head_dim_64_multi_head_takes_flat_kernel(self, monkeypatch):
        """Review catch: head_dim 64 with several local heads is bshd-
        ineligible — Ulysses must route through the bh-flat kernel path
        (impl='pallas' would raise on the bshd direct call), never the
        bshd XLA fallback that materializes full gathered-seq scores."""
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        sp = 2
        mesh = mesh_lib.make_mesh(context_parallel_size=sp)
        B, S, H, D = 1, 256, 4, 64
        q = jr.normal(K, (B, S, H, D)).astype(jnp.float32)
        k = jr.normal(jr.fold_in(K, 61), (B, S, H, D))
        v = jr.normal(jr.fold_in(K, 62), (B, S, H, D))
        with jax.default_matmul_precision("highest"):
            o = mesh_lib.shard_map(
                lambda q, k, v: ulysses_attention(q, k, v, causal=True,
                                                  impl="pallas"),
                mesh=mesh,
                in_specs=(P(None, "cp"),) * 3,
                out_specs=P(None, "cp"),
            )(q, k, v)
            ref = dense_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3), True).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(o, ref, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grouped_kv_matches_dense(self, causal):
        """GQA through Ulysses: q and kv scatter their own head counts (kv
        all_to_alls move group-times less data); flash handles grouping."""
        sp = 4
        mesh = mesh_lib.make_mesh(context_parallel_size=sp)
        B, S, H, HKV, D = 2, 32, 8, 4, 16
        q = jr.normal(K, (B, S, H, D))
        k = jr.normal(jr.fold_in(K, 21), (B, S, HKV, D))
        v = jr.normal(jr.fold_in(K, 22), (B, S, HKV, D))

        o = mesh_lib.shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, causal=causal),
            mesh=mesh,
            in_specs=(P(None, "cp"),) * 3,
            out_specs=P(None, "cp"),
        )(q, k, v)
        rep = H // HKV
        kr = jnp.repeat(k, rep, 2)
        vr = jnp.repeat(v, rep, 2)
        ref = dense_ref(q.transpose(0, 2, 1, 3), kr.transpose(0, 2, 1, 3),
                        vr.transpose(0, 2, 1, 3), causal).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(o, ref, rtol=RTOL, atol=ATOL)

    def test_grads_match_dense(self):
        sp = 4
        mesh = mesh_lib.make_mesh(context_parallel_size=sp)
        B, S, H, D = 1, 32, 4, 16
        q = jr.normal(K, (B, S, H, D))
        k = jr.normal(jr.fold_in(K, 23), (B, S, H, D))
        v = jr.normal(jr.fold_in(K, 24), (B, S, H, D))

        def local_loss(q, k, v):
            o = ulysses_attention(q, k, v, causal=True)
            return jnp.sum(o * o)

        g = jax.jit(mesh_lib.shard_map(
            lambda q, k, v: jax.grad(local_loss, argnums=(0, 1, 2))(q, k, v),
            mesh=mesh,
            in_specs=(P(None, "cp"),) * 3,
            out_specs=(P(None, "cp"),) * 3,
        ))(q, k, v)
        def ref_loss(q, k, v):
            o = dense_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), True)
            return jnp.sum(o * o)
        gref = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)
        for a, e in zip(g, gref):
            np.testing.assert_allclose(a, e, rtol=G_RTOL, atol=G_ATOL)

    def test_heads_not_divisible_raises(self):
        sp = 4
        mesh = mesh_lib.make_mesh(context_parallel_size=sp)
        q = jr.normal(K, (1, 32, 6, 16))  # 6 heads, sp=4
        with pytest.raises(ValueError, match="divisible"):
            mesh_lib.shard_map(
                lambda q: ulysses_attention(q, q, q),
                mesh=mesh, in_specs=(P(None, "cp"),),
                out_specs=P(None, "cp"),
            )(q)


class TestFlashAutoDispatch:
    def test_crossover_rule(self):
        """The measured auto-dispatch thresholds (PERF.md): 1024 at d=64,
        512 from d=128 — pinned so a dispatch edit can't silently flip
        which impl serves S in [512, 1024)."""
        from apex_tpu.ops.attention import flash_auto_crossover

        assert flash_auto_crossover(64) == 1024
        assert flash_auto_crossover(128) == 512
        assert flash_auto_crossover(256) == 512


class TestFlashDropout:
    """In-kernel attention dropout (the reference's fused-kernel capability
    — fmha_api.cpp:44,80-83 — rebuilt as a stateless counter-hash mask):
    kernel vs dense reference under the SAME mask, grads, determinism,
    dispatch-invariance, statistics."""

    RATE = 0.4

    def _dense_drop_ref(self, q, k, v, causal, scale, seed, rate,
                        kv_lens=None):
        """Dense oracle using the exact mask the kernels generate."""
        from apex_tpu.ops.attention import (_dropout_apply_dense,
                                            _dropout_keep_dense,
                                            masked_scores)

        s = masked_scores(q, k, scale, causal, kv_lens)
        lse = jax.nn.logsumexp(s, axis=-1)
        p = jnp.exp(s - lse[..., None])
        keep = _dropout_keep_dense(seed, s.shape[0], s.shape[-2],
                                   s.shape[-1], rate)
        return jnp.einsum("bqk,bkd->bqd",
                          _dropout_apply_dense(p, keep, rate), v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_kernel_matches_dense_same_mask(self, causal, monkeypatch):
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        bh, s, d = 3, 256, 64
        q = jr.normal(K, (bh, s, d))
        k = jr.normal(jr.fold_in(K, 50), (bh, s, d))
        v = jr.normal(jr.fold_in(K, 51), (bh, s, d))
        seed = jnp.int32(20240731)
        scale = 1.0 / d ** 0.5

        with jax.default_matmul_precision("highest"):
            f1 = lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
                q, k, v, causal=causal, impl="pallas",
                dropout_rate=self.RATE, dropout_seed=seed)))
            f2 = lambda q, k, v: jnp.sum(jnp.sin(self._dense_drop_ref(
                q, k, v, causal, scale, seed, self.RATE)))
            np.testing.assert_allclose(float(jax.jit(f1)(q, k, v)),
                                       float(jax.jit(f2)(q, k, v)),
                                       rtol=1e-5)
            g1 = jax.jit(jax.grad(f1, argnums=(0, 1, 2)))(q, k, v)
            g2 = jax.jit(jax.grad(f2, argnums=(0, 1, 2)))(q, k, v)
        for a, e, n in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(a, e, rtol=2e-4, atol=2e-5,
                                       err_msg=n)

    def test_gqa_kernel_matches_dense_same_mask(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        b, h, hkv, s, d = 2, 4, 2, 128, 64
        q = jr.normal(K, (b, h, s, d))
        k = jr.normal(jr.fold_in(K, 52), (b, hkv, s, d))
        v = jr.normal(jr.fold_in(K, 53), (b, hkv, s, d))
        seed = jnp.int32(7)
        scale = 1.0 / d ** 0.5
        rep = h // hkv

        with jax.default_matmul_precision("highest"):
            o = jax.jit(lambda q, k, v: flash_attention(
                q, k, v, causal=True, impl="pallas",
                dropout_rate=self.RATE, dropout_seed=seed))(q, k, v)
            ref = self._dense_drop_ref(
                q.reshape(b * h, s, d),
                jnp.repeat(k, rep, 1).reshape(b * h, s, d),
                jnp.repeat(v, rep, 1).reshape(b * h, s, d),
                True, scale, seed, self.RATE).reshape(b, h, s, d)
        np.testing.assert_allclose(o, ref, rtol=2e-5, atol=2e-5)

    def test_varlen_composes_with_dropout(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        bh, s, d = 4, 128, 64
        q = jr.normal(K, (bh, s, d))
        k = jr.normal(jr.fold_in(K, 54), (bh, s, d))
        v = jr.normal(jr.fold_in(K, 55), (bh, s, d))
        kv_lens = jnp.array([128, 96, 17, 0], jnp.int32)
        seed = jnp.int32(99)
        scale = 1.0 / d ** 0.5
        with jax.default_matmul_precision("highest"):
            o = flash_attention(q, k, v, kv_lens=kv_lens, impl="pallas",
                                dropout_rate=self.RATE, dropout_seed=seed)
            ref = self._dense_drop_ref(q, k, v, False, scale, seed,
                                       self.RATE, kv_lens=kv_lens)
            ref = jnp.where((kv_lens == 0)[:, None, None], 0.0, ref)
        np.testing.assert_allclose(o, ref, rtol=2e-5, atol=2e-5)

    def test_xla_and_pallas_masks_identical(self, monkeypatch):
        """The impl choice must never change a training run: both dispatches
        evaluate the same counter hash."""
        bh, s, d = 2, 256, 64
        q = jr.normal(K, (bh, s, d))
        k = jr.normal(jr.fold_in(K, 56), (bh, s, d))
        v = jr.normal(jr.fold_in(K, 57), (bh, s, d))
        seed = jnp.int32(5)
        with jax.default_matmul_precision("highest"):
            monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
            o_pl = flash_attention(q, k, v, causal=True, impl="pallas",
                                   dropout_rate=self.RATE, dropout_seed=seed)
            monkeypatch.delenv("APEX_TPU_PALLAS")
            o_xla = flash_attention(q, k, v, causal=True, impl="xla",
                                    dropout_rate=self.RATE,
                                    dropout_seed=seed)
        np.testing.assert_allclose(o_pl, o_xla, rtol=2e-5, atol=2e-5)

    def test_packed_fused_matches_bshd_same_seed(self, monkeypatch):
        """fused_qkv_attention's in-kernel dropout: same q-head grid index
        => same mask as the bshd composition; fwd + all cotangents."""
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        from apex_tpu.ops.attention import fused_qkv_attention

        # d=128: the bshd eligibility rule (128-lane folded blocks) must
        # hold for the composed reference path too
        b, s, H, h, d = 2, 128, 64, 2, 128
        hkv = 1
        G = h + 2 * hkv
        key = jr.fold_in(K, 58)
        x = jr.normal(key, (b, s, H))
        w_qkv = jr.normal(jr.fold_in(key, 1), (G * d, H)) * 0.1
        b_qkv = jr.normal(jr.fold_in(key, 2), (G * d,)) * 0.1
        w_out = jr.normal(jr.fold_in(key, 3), (H, h * d)) * 0.1
        scale = 1.0 / d ** 0.5
        seed = jnp.int32(11)

        def composed(x, w_qkv, b_qkv, w_out):
            qkv = jnp.einsum("bsH,FH->bsF", x, w_qkv) + b_qkv
            qkv = qkv.reshape(b, s, G, d)
            q, k, v = (qkv[:, :, :h], qkv[:, :, h:h + hkv],
                       qkv[:, :, h + hkv:])
            o = flash_attention(q, k, v, causal=True, layout="bshd",
                                impl="pallas", scale=scale,
                                dropout_rate=self.RATE, dropout_seed=seed)
            return jnp.einsum("bshd,Hhd->bsH", o, w_out.reshape(H, h, d))

        def fused(x, w_qkv, b_qkv, w_out):
            return fused_qkv_attention(x, w_qkv, b_qkv, w_out, None, seed,
                                       None, h, hkv, d, scale, True,
                                       self.RATE)

        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(fused(x, w_qkv, b_qkv, w_out),
                                       composed(x, w_qkv, b_qkv, w_out),
                                       rtol=2e-5, atol=2e-5)
            l1 = lambda *a: jnp.sum(jnp.sin(fused(*a)))
            l2 = lambda *a: jnp.sum(jnp.sin(composed(*a)))
            g1 = jax.jit(jax.grad(l1, argnums=(0, 1, 2, 3)))(x, w_qkv, b_qkv, w_out)
            g2 = jax.jit(jax.grad(l2, argnums=(0, 1, 2, 3)))(x, w_qkv, b_qkv, w_out)
        for a, e, n in zip(g1, g2, ("x", "w_qkv", "b_qkv", "w_out")):
            np.testing.assert_allclose(a, e, rtol=3e-4, atol=3e-5,
                                       err_msg=n)

    def test_determinism_and_seed_sensitivity(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        bh, s, d = 2, 128, 64
        q = jr.normal(K, (bh, s, d))
        k = jr.normal(jr.fold_in(K, 60), (bh, s, d))
        v = jr.normal(jr.fold_in(K, 61), (bh, s, d))
        run = lambda sd: flash_attention(
            q, k, v, causal=True, impl="pallas", dropout_rate=self.RATE,
            dropout_seed=jnp.int32(sd))
        a, b_, c = run(3), run(3), run(4)
        np.testing.assert_array_equal(a, b_)
        assert float(jnp.max(jnp.abs(a - c))) > 0.0

    def test_mask_statistics(self):
        """Keep fraction ~ (1-rate), E[mask_scale] ~ 1 (unbiasedness), and
        the mask is unbiased per row (the softmax-probs weighting)."""
        from apex_tpu.ops.attention import (_dropout_apply_dense,
                                            _dropout_keep_dense)

        ms = _dropout_apply_dense(
            jnp.float32(1.0),
            _dropout_keep_dense(jnp.int32(123), 8, 256, 256, self.RATE),
            self.RATE)
        keep_frac = float(jnp.mean(ms > 0))
        np.testing.assert_allclose(keep_frac, 1 - self.RATE, atol=5e-3)
        np.testing.assert_allclose(float(jnp.mean(ms)), 1.0, atol=2e-2)
        # per-row means concentrate around 1 — no row systematically dark
        row_means = jnp.mean(ms, axis=-1)
        assert float(jnp.max(jnp.abs(row_means - 1.0))) < 0.25

    def test_rate_validation(self):
        q = jr.normal(K, (2, 128, 64))
        with pytest.raises(ValueError, match="requires dropout_seed"):
            flash_attention(q, q, q, dropout_rate=0.1)
        with pytest.raises(ValueError, match="dropout_rate"):
            flash_attention(q, q, q, dropout_rate=1.5,
                            dropout_seed=jnp.int32(1))


class TestGPTFlashDropout:
    """GPT trains with dropout>0 ON the flash kernel paths (VERDICT r3
    missing #1: no more materialized-scores forfeit)."""

    def test_flash_dropout_trains_and_is_keyed(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        from apex_tpu.models import GPTConfig, GPTModel

        cfg = GPTConfig(vocab_size=64, max_seq_len=128, hidden_size=64,
                        num_layers=2, num_heads=1, dropout=0.2,
                        attention_impl="flash")
        m = GPTModel(cfg)
        p = m.init(jr.fold_in(K, 70))
        toks = jr.randint(jr.fold_in(K, 71), (2, 128), 0, 64)
        tgts = jr.randint(jr.fold_in(K, 72), (2, 128), 0, 64)

        loss_fn = lambda p, kk: m.loss_fn(p, toks, tgts, key=kk)
        l1, g = jax.jit(jax.value_and_grad(loss_fn))(p, jr.PRNGKey(1))
        l1b = loss_fn(p, jr.PRNGKey(1))
        l2 = loss_fn(p, jr.PRNGKey(2))
        l0 = m.loss_fn(p, toks, tgts)  # eval mode: no dropout
        assert jnp.isfinite(l1)
        assert float(l1) == float(l1b)  # keyed determinism
        assert float(l1) != float(l2)
        assert float(l1) != float(l0)
        for leaf in jax.tree.leaves(g):
            assert bool(jnp.all(jnp.isfinite(leaf)))


class TestVarlenFastPath:
    """kv_lens on the bshd and packed kernels (VERDICT r3 weak #5 / next
    #6): per-BATCH lengths ride the head-folded index maps; BERT's padded
    batches keep the zero-layout-copy route."""

    def _dense_varlen_ref(self, q4, k4, v4, lens, scale):
        """bhsd dense oracle from (b, s, h, d) operands + (b,) lengths."""
        b, s, h, d = q4.shape
        t = lambda z: z.transpose(0, 2, 1, 3).reshape(b * z.shape[2], s, d)
        from apex_tpu.ops.attention import _xla_attention
        o3, _ = _xla_attention(t(q4), t(k4), t(v4), scale, False,
                               jnp.repeat(lens, h))
        return o3.reshape(b, h, s, d).transpose(0, 2, 1, 3)

    @pytest.mark.parametrize("kv_heads", [2, 1])
    def test_bshd_kernel_varlen_matches_dense(self, kv_heads, monkeypatch):
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        b, s, h, d = 4, 256, 2, 128
        q = jr.normal(K, (b, s, h, d))
        k = jr.normal(jr.fold_in(K, 80), (b, s, kv_heads, d))
        v = jr.normal(jr.fold_in(K, 81), (b, s, kv_heads, d))
        lens = jnp.array([256, 130, 7, 0], jnp.int32)
        scale = 1.0 / d ** 0.5
        rep = h // kv_heads

        with jax.default_matmul_precision("highest"):
            f1 = lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
                q, k, v, kv_lens=lens, layout="bshd", impl="pallas")))
            ref = lambda q, k, v: jnp.sum(jnp.sin(self._dense_varlen_ref(
                q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2), lens,
                scale)))
            np.testing.assert_allclose(float(f1(q, k, v)),
                                       float(ref(q, k, v)), rtol=1e-5)
            g1 = jax.jit(jax.grad(f1, argnums=(0, 1, 2)))(q, k, v)
            g2 = jax.jit(jax.grad(ref, argnums=(0, 1, 2)))(q, k, v)
        for a, e, n in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(a, e, rtol=2e-4, atol=2e-5,
                                       err_msg=n)

    def test_bshd_varlen_with_dropout(self, monkeypatch):
        """varlen + in-kernel dropout compose on the bshd path."""
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        b, s, h, d = 2, 128, 1, 128
        q = jr.normal(K, (b, s, h, d))
        lens = jnp.array([128, 60], jnp.int32)
        seed = jnp.int32(3)
        o = flash_attention(q, q, q, kv_lens=lens, layout="bshd",
                            impl="pallas", dropout_rate=0.3,
                            dropout_seed=seed)
        o2 = flash_attention(q, q, q, kv_lens=lens, layout="bshd",
                             impl="xla", dropout_rate=0.3,
                             dropout_seed=seed)
        np.testing.assert_allclose(o, o2, rtol=2e-5, atol=2e-5)
        # masked-out tail of row 1 contributes nothing
        assert bool(jnp.all(jnp.isfinite(o)))

    def test_packed_fused_varlen_matches_bshd(self, monkeypatch):
        """fused_qkv_attention with kv_lens == the bshd composition —
        padded/ragged batches ride the zero-layout-copy block. Multi-block
        (s=256, bq=128 via block override is not exposed — use s=256 with
        default fitting) AND the two-kernel backward (varlen skips the
        single-block fused kernel)."""
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        from apex_tpu.ops.attention import fused_qkv_attention

        b, s, H, h, d = 2, 256, 64, 2, 128
        hkv = 2
        G = h + 2 * hkv
        key = jr.fold_in(K, 82)
        x = jr.normal(key, (b, s, H))
        w_qkv = jr.normal(jr.fold_in(key, 1), (G * d, H)) * 0.1
        b_qkv = jr.normal(jr.fold_in(key, 2), (G * d,)) * 0.1
        w_out = jr.normal(jr.fold_in(key, 3), (H, h * d)) * 0.1
        lens = jnp.array([256, 100], jnp.int32)
        scale = 1.0 / d ** 0.5

        def composed(x, w_qkv, b_qkv, w_out):
            qkv = jnp.einsum("bsH,FH->bsF", x, w_qkv) + b_qkv
            qkv = qkv.reshape(b, s, G, d)
            q, k, v = (qkv[:, :, :h], qkv[:, :, h:h + hkv],
                       qkv[:, :, h + hkv:])
            o = flash_attention(q, k, v, kv_lens=lens, layout="bshd",
                                impl="pallas", scale=scale, causal=True)
            return jnp.einsum("bshd,Hhd->bsH", o, w_out.reshape(H, h, d))

        def fused(x, w_qkv, b_qkv, w_out):
            return fused_qkv_attention(x, w_qkv, b_qkv, w_out, None, None,
                                       lens, h, hkv, d, scale, True)

        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(fused(x, w_qkv, b_qkv, w_out),
                                       composed(x, w_qkv, b_qkv, w_out),
                                       rtol=2e-5, atol=2e-5)
            l1 = lambda *a: jnp.sum(jnp.sin(fused(*a)))
            l2 = lambda *a: jnp.sum(jnp.sin(composed(*a)))
            g1 = jax.jit(jax.grad(l1, argnums=(0, 1, 2, 3)))(x, w_qkv, b_qkv, w_out)
            g2 = jax.jit(jax.grad(l2, argnums=(0, 1, 2, 3)))(x, w_qkv, b_qkv, w_out)
        for a, e, n in zip(g1, g2, ("x", "w_qkv", "b_qkv", "w_out")):
            np.testing.assert_allclose(a, e, rtol=3e-4, atol=3e-5,
                                       err_msg=n)

    def test_bshd_rejects_wrong_lens_shape(self):
        q = jr.normal(K, (2, 128, 1, 128))
        with pytest.raises(ValueError, match="per-batch kv_lens"):
            flash_attention(q, q, q, layout="bshd",
                            kv_lens=jnp.zeros((2, 1), jnp.int32))

    def test_bert_varlen_rides_bshd_kernels(self, monkeypatch):
        """BERT with suffix padding on a bshd-eligible config (d=128):
        flash == softmax impl, and the flash path goes through the bshd
        kernels (interpret forced so the kernel code actually runs)."""
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        from apex_tpu.models import BertConfig, BertModel

        kw = dict(vocab_size=64, max_seq_len=128, hidden_size=256,
                  num_layers=2, num_heads=2)  # head_dim 128: bshd-eligible
        m_f = BertModel(BertConfig(**kw, attention_impl="flash"))
        m_s = BertModel(BertConfig(**kw, attention_impl="softmax"))
        params = m_f.init(jr.fold_in(K, 83))
        b, s = 2, 128
        toks = jr.randint(jr.fold_in(K, 84), (b, s), 0, 64)
        # suffix padding: row 0 full, row 1 valid through 57
        pad_mask = jnp.arange(s)[None, :] >= jnp.array([[s], [57]])
        with jax.default_matmul_precision("highest"):
            h_f = m_f.hidden_states(params, toks, pad_mask=pad_mask)
            h_s = m_s.hidden_states(params, toks, pad_mask=pad_mask)
        # only VALID positions must agree (padding rows see garbage keys
        # in neither impl but their outputs are don't-care)
        np.testing.assert_allclose(h_f[0], h_s[0], rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(h_f[1, :57], h_s[1, :57], rtol=1e-4,
                                   atol=1e-4)


class TestCpDropout:
    """Dropout x context parallelism (r4 late): ring folds a distinct mask
    stream per (rank, step, piece) and re-derives it in its hand-written
    backward; ulysses folds the cp rank into the seed."""

    RATE = 0.3

    def _mesh(self):
        return mesh_lib.make_mesh(context_parallel_size=2)

    def test_ring_dropout_grads_match_autodiff(self):
        """The exactness witness: the custom VJP (hand-written piece
        backward with re-derived seeds) against plain autodiff through the
        forward implementation — any fwd/bwd mask inconsistency breaks
        this."""
        from apex_tpu.ops.attention import _ring_fwd_impl, ring_attention

        mesh = self._mesh()
        bh, s, d = 2, 64, 16  # XLA piece path (differentiable)
        seed = jnp.int32(77)
        q = jr.normal(K, (bh, 2 * s, d))
        k = jr.normal(jr.fold_in(K, 90), (bh, 2 * s, d))
        v = jr.normal(jr.fold_in(K, 91), (bh, 2 * s, d))

        def custom(q, k, v):
            o = ring_attention(q, k, v, axis_name="cp", causal=True,
                               impl="xla", dropout_rate=self.RATE,
                               dropout_seed=seed)
            return jnp.sum(jnp.sin(o))

        def auto(q, k, v):
            o, _ = _ring_fwd_impl(q, k, v, "cp", 1.0 / d ** 0.5, True,
                                  False, self.RATE, seed)
            return jnp.sum(jnp.sin(o))

        def run(q, k, v):
            g1 = jax.grad(custom, argnums=(0, 1, 2))(q, k, v)
            g2 = jax.grad(auto, argnums=(0, 1, 2))(q, k, v)
            return g1, g2

        from apex_tpu.ops.attention import zigzag_shard
        qz, kz, vz = (zigzag_shard(x, 2, 1) for x in (q, k, v))
        with jax.default_matmul_precision("highest"):
            g1, g2 = jax.jit(mesh_lib.shard_map(
                run, mesh=mesh, in_specs=(P(None, "cp"),) * 3,
                out_specs=((P(None, "cp"),) * 3,) * 2,
            ))(qz, kz, vz)
        for a, e, n in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(a, e, rtol=2e-4, atol=2e-5,
                                       err_msg=n)

    def test_ring_dropout_deterministic_and_live(self):
        from apex_tpu.ops.attention import ring_attention, zigzag_shard

        mesh = self._mesh()
        bh, s, d = 2, 128, 64
        q = jr.normal(K, (bh, 2 * s, d))
        run = lambda sd: jax.jit(mesh_lib.shard_map(
            lambda q_: ring_attention(q_, q_, q_, axis_name="cp",
                                      causal=True, impl="xla",
                                      dropout_rate=self.RATE,
                                      dropout_seed=jnp.int32(sd)),
            mesh=mesh, in_specs=P(None, "cp"), out_specs=P(None, "cp"),
        ))(zigzag_shard(q, 2, 1))
        a, b_, c = run(5), run(5), run(6)
        np.testing.assert_array_equal(a, b_)
        assert float(jnp.max(jnp.abs(a - c))) > 0.0

    def test_ulysses_dropout_matches_per_rank_reference(self, monkeypatch):
        """Each device computes its head group with seed fold(base, rank);
        the host can replay exactly that — outputs must match."""
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        from apex_tpu.ops.attention import (flash_attention,
                                            fold_dropout_seed,
                                            ulysses_attention)

        mesh = self._mesh()
        b, s, h, d = 2, 128, 2, 128
        base = jnp.int32(13)
        q = jr.normal(K, (b, s, h, d))
        k = jr.normal(jr.fold_in(K, 92), (b, s, h, d))
        v = jr.normal(jr.fold_in(K, 93), (b, s, h, d))

        o = jax.jit(mesh_lib.shard_map(
            lambda q_, k_, v_: ulysses_attention(
                q_, k_, v_, axis_name="cp", causal=True, impl="pallas",
                dropout_rate=self.RATE, dropout_seed=base),
            mesh=mesh, in_specs=(P(None, "cp"),) * 3,
            out_specs=P(None, "cp"),
        ))(q, k, v)

        # host replay: rank r holds head group r (h/cp heads each)
        with jax.default_matmul_precision("highest"):
            parts = [
                flash_attention(
                    q[:, :, r:r + 1], k[:, :, r:r + 1], v[:, :, r:r + 1],
                    causal=True, layout="bshd", impl="pallas",
                    dropout_rate=self.RATE,
                    dropout_seed=fold_dropout_seed(base, r))
                for r in range(2)]
        ref = jnp.concatenate(parts, axis=2)
        np.testing.assert_allclose(o, ref, rtol=2e-5, atol=2e-5)

    def test_ring_rejects_missing_seed(self):
        q = jr.normal(K, (2, 64, 16))
        mesh = self._mesh()
        from apex_tpu.ops.attention import ring_attention
        with pytest.raises(ValueError, match="requires dropout_seed"):
            mesh_lib.shard_map(
                lambda q_: ring_attention(q_, q_, q_, axis_name="cp",
                                          dropout_rate=0.1),
                mesh=mesh, in_specs=P(None, "cp"),
                out_specs=P(None, "cp"))(q)


class TestRingBshd:
    """Ring attention on the seq-major layout (r4 late): the stripe pieces
    ride the bshd kernels — no transpose round trip per ring step."""

    def _mesh(self):
        return mesh_lib.make_mesh(context_parallel_size=2)

    @pytest.mark.parametrize("kv_heads", [2, 1])
    def test_bshd_ring_matches_flash(self, kv_heads, monkeypatch):
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        mesh = self._mesh()
        b, s, h, d = 2, 512, 2, 128  # s_local 256, stripes 128
        q = jr.normal(K, (b, s, h, d))
        k = jr.normal(jr.fold_in(K, 95), (b, s, kv_heads, d))
        v = jr.normal(jr.fold_in(K, 96), (b, s, kv_heads, d))

        def run(q_, k_, v_):
            return ring_attention(q_, k_, v_, axis_name="cp", causal=True,
                                  layout="bshd", impl="pallas")

        qz, kz, vz = (zigzag_shard(x, 2, 1) for x in (q, k, v))
        with jax.default_matmul_precision("highest"):
            o = jax.jit(mesh_lib.shard_map(
                run, mesh=mesh, in_specs=(P(None, "cp"),) * 3,
                out_specs=P(None, "cp"),
            ))(qz, kz, vz)
            o = zigzag_unshard(o, 2, 1)
            ref = flash_attention(q, k, v, causal=True, layout="bshd",
                                  impl="pallas")
        np.testing.assert_allclose(o, ref, rtol=2e-4, atol=2e-5)

    def test_bshd_ring_grads_match_flat_ring(self):
        """Same math, two layouts: grads through the bshd state machine
        must equal the flat one's (which is itself pinned to dense)."""
        mesh = self._mesh()
        b, s, h, d = 2, 128, 2, 64
        q = jr.normal(K, (b, s, h, d))
        k = jr.normal(jr.fold_in(K, 97), (b, s, h, d))
        v = jr.normal(jr.fold_in(K, 98), (b, s, h, d))
        to_bh = lambda z: z.transpose(0, 2, 1, 3).reshape(b * h, s, d)

        def run_bshd(q_, k_, v_):
            f = lambda *a: jnp.sum(jnp.sin(ring_attention(
                *a, axis_name="cp", causal=True, layout="bshd",
                impl="xla")))
            return jax.grad(f, argnums=(0, 1, 2))(q_, k_, v_)

        def run_flat(q_, k_, v_):
            f = lambda *a: jnp.sum(jnp.sin(ring_attention(
                *a, axis_name="cp", causal=True, impl="xla")))
            return jax.grad(f, argnums=(0, 1, 2))(q_, k_, v_)

        with jax.default_matmul_precision("highest"):
            qz, kz, vz = (zigzag_shard(x, 2, 1) for x in (q, k, v))
            g4 = jax.jit(mesh_lib.shard_map(
                run_bshd, mesh=mesh, in_specs=(P(None, "cp"),) * 3,
                out_specs=(P(None, "cp"),) * 3,
            ))(qz, kz, vz)
            qf, kf, vf = (zigzag_shard(to_bh(x), 2, 1) for x in (q, k, v))
            gf = jax.jit(mesh_lib.shard_map(
                run_flat, mesh=mesh, in_specs=(P(None, "cp"),) * 3,
                out_specs=(P(None, "cp"),) * 3,
            ))(qf, kf, vf)
        for a4, af, n in zip(g4, gf, "qkv"):
            a4f = zigzag_unshard(a4, 2, 1)
            aff = zigzag_unshard(af, 2, 1).reshape(b, h, s, d
                                                   ).transpose(0, 2, 1, 3)
            np.testing.assert_allclose(a4f, aff, rtol=2e-4, atol=2e-5,
                                       err_msg=n)

    def test_bshd_ring_dropout_grads_match_autodiff(self):
        """The dropout mask-consistency witness on the bshd state machine
        (custom VJP vs autodiff through the forward)."""
        from apex_tpu.ops.attention import _ring_fwd_impl

        mesh = self._mesh()
        b, s, h, d = 1, 128, 2, 16
        seed = jnp.int32(88)
        q = jr.normal(K, (b, s, h, d))
        k = jr.normal(jr.fold_in(K, 99), (b, s, h, d))
        v = jr.normal(jr.fold_in(K, 100), (b, s, h, d))

        def custom(q_, k_, v_):
            o = ring_attention(q_, k_, v_, axis_name="cp", causal=True,
                               layout="bshd", impl="xla",
                               dropout_rate=0.3, dropout_seed=seed)
            return jnp.sum(jnp.sin(o))

        def auto(q_, k_, v_):
            o, _ = _ring_fwd_impl(q_, k_, v_, "cp", 1.0 / d ** 0.5, True,
                                  False, 0.3, seed, True)
            return jnp.sum(jnp.sin(o))

        def run(q_, k_, v_):
            return (jax.grad(custom, argnums=(0, 1, 2))(q_, k_, v_),
                    jax.grad(auto, argnums=(0, 1, 2))(q_, k_, v_))

        qz, kz, vz = (zigzag_shard(x, 2, 1) for x in (q, k, v))
        with jax.default_matmul_precision("highest"):
            g1, g2 = jax.jit(mesh_lib.shard_map(
                run, mesh=mesh, in_specs=(P(None, "cp"),) * 3,
                out_specs=((P(None, "cp"),) * 3,) * 2,
            ))(qz, kz, vz)
        for a, e, n in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(a, e, rtol=2e-4, atol=2e-5,
                                       err_msg=n)

    def test_bshd_ring_pallas_bwd_matches_xla_dispatch(self, monkeypatch):
        """The production path's backward (Pallas bshd piece kernels with
        the ring's GLOBAL lse + per-piece dropout seeds) against the XLA
        dispatch — masks are bit-identical across dispatches by design,
        so grads must agree."""
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        mesh = self._mesh()
        b, s, h, d = 2, 512, 2, 128
        seed = jnp.int32(21)
        q = jr.normal(K, (b, s, h, d))
        k = jr.normal(jr.fold_in(K, 101), (b, s, 1, d))  # GQA group 2
        v = jr.normal(jr.fold_in(K, 102), (b, s, 1, d))

        def make(impl):
            def f(q_, k_, v_):
                o = ring_attention(q_, k_, v_, axis_name="cp",
                                   causal=True, layout="bshd", impl=impl,
                                   dropout_rate=0.3, dropout_seed=seed)
                return jnp.sum(jnp.sin(o))
            def run(q_, k_, v_):
                return jax.grad(f, argnums=(0, 1, 2))(q_, k_, v_)
            return jax.jit(mesh_lib.shard_map(
                run, mesh=mesh, in_specs=(P(None, "cp"),) * 3,
                out_specs=(P(None, "cp"),) * 3))

        qz, kz, vz = (zigzag_shard(x, 2, 1) for x in (q, k, v))
        with jax.default_matmul_precision("highest"):
            g_pl = make("pallas")(qz, kz, vz)
            g_xla = make("xla")(qz, kz, vz)
        for a, e, n in zip(g_pl, g_xla, "qkv"):
            np.testing.assert_allclose(a, e, rtol=3e-4, atol=3e-5,
                                       err_msg=n)

    def test_bshd_ring_rejects_mismatched_seq(self):
        mesh = self._mesh()
        q = jr.normal(K, (1, 128, 2, 128))
        k = jr.normal(K, (1, 256, 2, 128))
        with pytest.raises(ValueError, match="equal q/k/v local sequence"):
            mesh_lib.shard_map(
                lambda q_, k_: ring_attention(q_, k_, k_, axis_name="cp",
                                              layout="bshd"),
                mesh=mesh, in_specs=(P(None, "cp"), P(None, "cp")),
                out_specs=P(None, "cp"))(q, k)


class TestFlashBias:
    """In-kernel additive score bias (VERDICT r4 next #1): the reference
    fuses arbitrary masks into its softmax kernels
    (``csrc/megatron/scaled_masked_softmax.cpp:85-94``) and ships additive
    attn_mask MHA variants (``contrib/multihead_attn/self_multihead_attn
    .py:144-198``); here one (hb, sq, sk) bias operand rides every flash
    layout, differentiated via the batch-innermost dbias kernel."""

    def _dense_bias(self, q, k, v, bias, causal, kv_lens=None):
        """Dense oracle: rows of the flattened leading dims read bias row
        r % hb; bias adds to the SCALED scores before masks."""
        d = q.shape[-1]
        lead = q.shape[:-2]
        sq, sk = q.shape[-2], k.shape[-2]
        q3 = q.reshape(-1, sq, d)
        k3 = k.reshape(-1, sk, d)
        v3 = v.reshape(-1, sk, d)
        g = q3.shape[0] // k3.shape[0]
        if g > 1:
            k3 = jnp.repeat(k3, g, 0)
            v3 = jnp.repeat(v3, g, 0)
        hb = bias.shape[0]
        s = jnp.einsum("bqd,bkd->bqk", q3, k3) / d ** 0.5
        s = (s.reshape(-1, hb, sq, sk) + bias).reshape(-1, sq, sk)
        if causal:
            m = jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None] + (sk - sq)
            s = jnp.where(m, s, -1e30)
        if kv_lens is not None:
            s = jnp.where(jnp.arange(sk)[None, None, :]
                          < kv_lens[:, None, None], s, -1e30)
        o = jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v3)
        return o.reshape(*lead, sq, d)

    @pytest.mark.pallas
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("hb", [1, 2])  # broadcast | per-head
    def test_kernel_fwd_bwd_vs_dense(self, causal, hb, monkeypatch):
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        b, h, s, d = 2, 2, 128, 64
        q = jr.normal(K, (b, h, s, d))
        k = jr.normal(jr.fold_in(K, 1), (b, h, s, d))
        v = jr.normal(jr.fold_in(K, 2), (b, h, s, d))
        bias = jr.normal(jr.fold_in(K, 3), (hb, s, s)) * 0.5

        def f(q, k, v, bias):
            return jnp.sum(jnp.sin(flash_attention(
                q, k, v, causal=causal, bias=bias, impl="pallas")))

        def ref(q, k, v, bias):
            return jnp.sum(jnp.sin(self._dense_bias(q, k, v, bias, causal)))

        with jax.default_matmul_precision("highest"):
            o = flash_attention(q, k, v, causal=causal, bias=bias,
                                impl="pallas")
            np.testing.assert_allclose(
                o, self._dense_bias(q, k, v, bias, causal),
                rtol=1e-4, atol=1e-4)
            g1 = jax.jit(jax.grad(f, (0, 1, 2, 3)))(q, k, v, bias)
            g2 = jax.jit(jax.grad(ref, (0, 1, 2, 3)))(q, k, v, bias)
        for a, e, n in zip(g1, g2, ["dq", "dk", "dv", "dbias"]):
            np.testing.assert_allclose(a, e, rtol=5e-4, atol=5e-4,
                                       err_msg=n)

    @pytest.mark.pallas
    def test_bshd_composed_gqa_varlen_dropout(self, monkeypatch):
        """All the operands at once on the seq-major layout: per-head
        bias + grouped kv + padded batch + in-kernel dropout — Pallas
        vs XLA dispatch (same mask hash, same bias math)."""
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        b, s, h, hkv, d = 2, 256, 4, 2, 128
        q = jr.normal(K, (b, s, h, d))
        k = jr.normal(jr.fold_in(K, 4), (b, s, hkv, d))
        v = jr.normal(jr.fold_in(K, 5), (b, s, hkv, d))
        bias = jr.normal(jr.fold_in(K, 6), (h, s, s)) * 0.5
        lens = jnp.array([200, 128], jnp.int32)

        def make(impl):
            def f(q, k, v, bias):
                return jnp.sum(jnp.sin(flash_attention(
                    q, k, v, causal=True, bias=bias, kv_lens=lens,
                    layout="bshd", impl=impl, dropout_rate=0.15,
                    dropout_seed=7)))
            return f

        with jax.default_matmul_precision("highest"):
            o1 = flash_attention(q, k, v, causal=True, bias=bias,
                                 kv_lens=lens, layout="bshd",
                                 impl="pallas", dropout_rate=0.15,
                                 dropout_seed=7)
            o2 = flash_attention(q, k, v, causal=True, bias=bias,
                                 kv_lens=lens, layout="bshd", impl="xla",
                                 dropout_rate=0.15, dropout_seed=7)
            np.testing.assert_allclose(o1, o2, rtol=5e-4, atol=5e-4)
            g1 = jax.jit(jax.grad(make("pallas"), (0, 1, 2, 3)))(q, k, v, bias)
            g2 = jax.jit(jax.grad(make("xla"), (0, 1, 2, 3)))(q, k, v, bias)
        for a, e, n in zip(g1, g2, ["dq", "dk", "dv", "dbias"]):
            np.testing.assert_allclose(a, e, rtol=2e-3, atol=2e-3,
                                       err_msg=n)

    @pytest.mark.pallas
    def test_packed_fused_qkv_bias_grads(self, monkeypatch):
        """fused_qkv_attention with bias == the composed bshd path,
        through every weight gradient plus dbias."""
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        from apex_tpu.ops.attention import (bshd_output_projection,
                                            bshd_qkv_projection,
                                            fused_qkv_attention)
        b, s, h, hkv, d = 2, 128, 2, 1, 128
        H = h * d
        x = jr.normal(K, (b, s, H)) * 0.3
        w_qkv = jr.normal(jr.fold_in(K, 7), ((h + 2 * hkv) * d, H)) * 0.05
        b_qkv = jr.normal(jr.fold_in(K, 8), ((h + 2 * hkv) * d,)) * 0.02
        w_out = jr.normal(jr.fold_in(K, 9), (H, h * d)) * 0.05
        bias = jr.normal(jr.fold_in(K, 10), (h, s, s)) * 0.5
        scale = 1.0 / d ** 0.5

        def fused(x, w_qkv, b_qkv, w_out, bias):
            return fused_qkv_attention(x, w_qkv, b_qkv, w_out, bias, None,
                                       None, h, hkv, d, scale, True).sum()

        def composed(x, w_qkv, b_qkv, w_out, bias):
            qq, kq, vq = bshd_qkv_projection(x, w_qkv, b_qkv, h, hkv, d)
            ctx = flash_attention(qq, kq, vq, causal=True, bias=bias,
                                  layout="bshd", impl="xla")
            return bshd_output_projection(ctx, w_out, h, d).sum()

        with jax.default_matmul_precision("highest"):
            ga = jax.jit(jax.grad(fused, (0, 1, 2, 3, 4)))(
                x, w_qkv, b_qkv, w_out, bias)
            gb = jax.jit(jax.grad(composed, (0, 1, 2, 3, 4)))(
                x, w_qkv, b_qkv, w_out, bias)
        for a, e, n in zip(ga, gb, ["dx", "dw_qkv", "db_qkv", "dw_out",
                                    "dbias"]):
            np.testing.assert_allclose(a, e, rtol=2e-3, atol=2e-3,
                                       err_msg=n)

    def test_bias_validation(self):
        q = jr.normal(K, (2, 4, 128, 64))
        with pytest.raises(ValueError, match="bias must be"):
            flash_attention(q, q, q, bias=jnp.zeros((4, 64, 64)))
        with pytest.raises(ValueError, match="bias rows"):
            flash_attention(q, q, q, bias=jnp.zeros((3, 128, 128)))
        qs = jr.normal(K, (2, 128, 4, 64))
        with pytest.raises(ValueError, match="dividing"):
            flash_attention(qs, qs, qs, layout="bshd",
                            bias=jnp.zeros((3, 128, 128)))


class TestBucketedBias:
    """In-kernel BUCKETED relative bias (VERDICT r5 missing #2 + #1): the
    (num_buckets, h) table rides into VMEM and every score tile
    recomputes its bias from the closed form — no (h, sq, sk) array
    exists on the kernel path (jaxpr-asserted below) — and, because the
    bias derives from GLOBAL offsets, the same operand is first-class
    under ring/ulysses context parallelism."""

    def _bb(self, tab, bidir, maxd=64):
        from apex_tpu.ops.attention import BucketedBias
        return BucketedBias(tab, bidirectional=bidir, max_distance=maxd)

    @pytest.mark.pallas
    @pytest.mark.parametrize("causal,bidir", [(False, True), (True, False)])
    def test_kernel_fwd_bwd_vs_materialized(self, causal, bidir,
                                            monkeypatch):
        """Pallas in-kernel recompute == the materialized-operand oracle,
        through dq/dk/dv AND the bucket-table grad (dtable kernel vs the
        gather VJP)."""
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        b, h, s, d = 2, 2, 128, 64
        q = jr.normal(K, (b, h, s, d))
        k = jr.normal(jr.fold_in(K, 1), (b, h, s, d))
        v = jr.normal(jr.fold_in(K, 2), (b, h, s, d))
        tab = jr.normal(jr.fold_in(K, 3), (32, h)) * 0.4

        def bucketed(q, k, v, t):
            return jnp.sum(jnp.sin(flash_attention(
                q, k, v, causal=causal, bias=self._bb(t, bidir),
                impl="pallas")))

        def oracle(q, k, v, t):
            arr = self._bb(t, bidir).materialize(s, s)
            return jnp.sum(jnp.sin(flash_attention(
                q, k, v, causal=causal, bias=arr,  # apexlint: disable=APX304
                impl="xla")))

        with jax.default_matmul_precision("highest"):
            o1 = jax.jit(lambda q, k, v, t: flash_attention(
                q, k, v, causal=causal, bias=self._bb(t, bidir),
                impl="pallas"))(q, k, v, tab)
            o2 = flash_attention(q, k, v, causal=causal,
                                 bias=self._bb(tab, bidir).materialize(s, s),  # apexlint: disable=APX304
                                 impl="xla")
            np.testing.assert_allclose(o1, o2, rtol=1e-4, atol=1e-4)
            g1 = jax.jit(jax.grad(bucketed, (0, 1, 2, 3)))(q, k, v, tab)
            g2 = jax.jit(jax.grad(oracle, (0, 1, 2, 3)))(q, k, v, tab)
        for a, e, n in zip(g1, g2, ["dq", "dk", "dv", "dtable"]):
            np.testing.assert_allclose(a, e, rtol=5e-4, atol=5e-4,
                                       err_msg=n)

    @pytest.mark.pallas
    def test_bshd_composed_gqa_varlen_dropout(self, monkeypatch):
        """All operands at once on the seq-major layout: bucketed bias +
        grouped kv + padded batch + in-kernel dropout — Pallas vs XLA
        dispatch (same hash, same closed form)."""
        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        b, s, h, hkv, d = 2, 256, 4, 2, 128
        q = jr.normal(K, (b, s, h, d))
        k = jr.normal(jr.fold_in(K, 4), (b, s, hkv, d))
        v = jr.normal(jr.fold_in(K, 5), (b, s, hkv, d))
        tab = jr.normal(jr.fold_in(K, 6), (32, h)) * 0.4
        lens = jnp.array([200, 128], jnp.int32)

        def make(impl):
            def f(q, k, v, t):
                return jnp.sum(jnp.sin(flash_attention(
                    q, k, v, causal=True, bias=self._bb(t, False),
                    kv_lens=lens, layout="bshd", impl=impl,
                    dropout_rate=0.15, dropout_seed=7)))
            return f

        with jax.default_matmul_precision("highest"):
            g1 = jax.jit(jax.grad(make("pallas"), (0, 1, 2, 3)))(q, k, v, tab)
            g2 = jax.jit(jax.grad(make("xla"), (0, 1, 2, 3)))(q, k, v, tab)
        for a, e, n in zip(g1, g2, ["dq", "dk", "dv", "dtable"]):
            np.testing.assert_allclose(a, e, rtol=2e-3, atol=2e-3,
                                       err_msg=n)

    def test_offsets_select_the_global_window(self):
        """A shifted BucketedBias materializes the corresponding window of
        the global bias — the property the cp paths are built on."""
        tab = jr.normal(jr.fold_in(K, 7), (16, 3)) * 0.5
        bb = self._bb(tab, True, 32)
        full = bb.materialize(512, 512)
        win = bb.shifted(128, 256).materialize(64, 128)
        np.testing.assert_allclose(win, full[:, 128:192, 256:384])

    @pytest.mark.pallas
    def test_no_materialized_bias_in_jaxpr(self, monkeypatch):
        """THE memory claim, statically: the jaxpr of the bucketed kernel
        path (fwd AND grad) contains NO intermediate with two >= seq
        dims — the O(h·s²) bias (and any O(s²) score tensor) never
        exists. The 512-block cap died with it (blocks follow normal
        sizing). Asserted through the shared JXP contract helper
        (``apex_tpu.lint.contracts.no_aval_matching``), which carries
        the same Pallas-body exemption this test used to hand-roll: the
        kernel BODY works on (bq, bk) VMEM tiles — which equal (s, s)
        at this size — while the claim is about HBM arrays, i.e. the
        kernel's operands (checked at the pallas_call eqn) and
        everything outside the kernel."""
        from apex_tpu.lint import contracts as jc

        monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
        s, h, d = 256, 2, 64
        q = jr.normal(K, (h, s, d))
        tab = jr.normal(jr.fold_in(K, 8), (32, h)) * 0.4

        def fwd(q, k, v, t):
            return flash_attention(q, k, v, causal=False,
                                   bias=self._bb(t, True), impl="pallas")

        def loss(q, k, v, t):
            return jnp.sum(fwd(q, k, v, t) ** 2)

        contract = jc.no_aval_matching(
            lambda shape: sum(1 for dim in shape if dim >= s) >= 2,
            f"two dims >= seq ({s}): a materialized O(s^2) bias/score")
        for fn in (fwd, jax.grad(loss, argnums=(0, 1, 2, 3))):
            jc.assert_contracts(jax.make_jaxpr(fn)(q, q, q, tab),
                                [contract])

    def test_ring_bias_and_kv_lens_match_flash(self):
        """The cp seam (VERDICT r5 missing #1): ring attention with the
        bucketed bias + GLOBAL kv_lens (including a fully-dead row) ==
        single-chip flash with the same operands — outputs and all four
        grads, causal (zigzag stripes, step-0 three-piece decomposition)
        and full."""
        cp = 4
        mesh = mesh_lib.make_mesh(context_parallel_size=cp)
        bh, s, d, heads = 4, 16 * cp, 16, 2
        q = jr.normal(K, (bh, s, d))
        k = jr.normal(jr.fold_in(K, 9), (bh, s, d))
        v = jr.normal(jr.fold_in(K, 10), (bh, s, d))
        tab = jr.normal(jr.fold_in(K, 11), (16, heads)) * 0.4
        lens = jnp.array([s, 37, 20, 0], jnp.int32)

        for causal in (True, False):
            bidir = not causal

            def ring_loss(q, k, v, t):
                o = ring_attention(q, k, v, axis_name="cp", causal=causal,
                                   kv_lens=lens, bias=self._bb(t, bidir))
                return jnp.sum(jnp.sin(o))

            def flash_loss(q, k, v, t):
                o = flash_attention(q, k, v, causal=causal, kv_lens=lens,
                                    bias=self._bb(t, bidir))
                return jnp.sum(jnp.sin(o))

            spec = P(None, "cp", None)
            with jax.default_matmul_precision("highest"):
                if causal:
                    qs, ks, vs = (zigzag_shard(x, cp, 1)
                                  for x in (q, k, v))
                else:
                    qs, ks, vs = q, k, v
                g = jax.jit(mesh_lib.shard_map(
                    lambda q, k, v, t: jax.grad(
                        ring_loss, argnums=(0, 1, 2, 3))(q, k, v, t),
                    mesh=mesh, in_specs=(spec,) * 3 + (P(),),
                    out_specs=(spec,) * 3 + (P(),),
                ))(qs, ks, vs, tab)
                gref = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2, 3)))(
                    q, k, v, tab)
            for i, (a, e, n) in enumerate(
                    zip(g, gref, ["dq", "dk", "dv", "dtable"])):
                if causal and i < 3:
                    a = zigzag_unshard(a, cp, 1)
                np.testing.assert_allclose(
                    a, e, rtol=2e-3, atol=2e-3,
                    err_msg=f"{n} causal={causal}")

    def test_ulysses_bias_and_kv_lens_match_flash(self):
        """Ulysses: per-head table slices to each rank's head group (grad
        scatters + psums back), kv_lens rides the gathered sequence."""
        cp = 2
        mesh = mesh_lib.make_mesh(context_parallel_size=cp)
        b, s, h, d = 2, 32 * cp, 4, 16
        q = jr.normal(K, (b, s, h, d))
        k = jr.normal(jr.fold_in(K, 12), (b, s, h, d))
        v = jr.normal(jr.fold_in(K, 13), (b, s, h, d))
        tab = jr.normal(jr.fold_in(K, 14), (16, h)) * 0.4
        lens = jnp.array([40, 0], jnp.int32)

        def u_loss(q, k, v, t):
            o = ulysses_attention(q, k, v, axis_name="cp", causal=True,
                                  kv_lens=lens, bias=self._bb(t, False))
            return jnp.sum(jnp.sin(o))

        def f_loss(q, k, v, t):
            o = flash_attention(q, k, v, causal=True, kv_lens=lens,
                                bias=self._bb(t, False), layout="bshd")
            return jnp.sum(jnp.sin(o))

        spec = P(None, "cp")
        with jax.default_matmul_precision("highest"):
            g = jax.jit(mesh_lib.shard_map(
                lambda q, k, v, t: jax.grad(
                    u_loss, argnums=(0, 1, 2, 3))(q, k, v, t),
                mesh=mesh, in_specs=(spec,) * 3 + (P(),),
                out_specs=(spec,) * 3 + (P(),),
            ))(q, k, v, tab)
            gref = jax.jit(jax.grad(f_loss, argnums=(0, 1, 2, 3)))(
                q, k, v, tab)
        for a, e, n in zip(g, gref, ["dq", "dk", "dv", "dtable"]):
            np.testing.assert_allclose(a, e, rtol=2e-3, atol=2e-3,
                                       err_msg=n)

    def test_validation(self):
        from apex_tpu.ops.attention import BucketedBias
        q = jr.normal(K, (2, 4, 128, 64))
        with pytest.raises(ValueError, match="num_buckets"):
            flash_attention(q, q, q, bias=BucketedBias(
                jnp.zeros((130, 4)), True, 64))
        with pytest.raises(ValueError, match="even num_buckets"):
            flash_attention(q, q, q, bias=BucketedBias(
                jnp.zeros((15, 4)), True, 64))
        with pytest.raises(ValueError, match="divide"):
            flash_attention(q, q, q, bias=BucketedBias(
                jnp.zeros((16, 3)), True, 64))
        with pytest.raises(ValueError, match="BucketedBias"):
            ring_attention(q[:, 0], q[:, 0], q[:, 0],
                           bias=jnp.zeros((4, 128, 128)))
        with pytest.raises(ValueError, match="materialized"):
            from apex_tpu.ops.attention import fused_qkv_attention
            fused_qkv_attention(
                jnp.zeros((1, 128, 64)), jnp.zeros((192, 64)),
                jnp.zeros((192,)), jnp.zeros((64, 64)),
                BucketedBias(jnp.zeros((16, 1)), True, 64), None, None,
                1, 1, 64, 0.125, True)


# --- the forward's three kinds of tile ----------------------------------------
#
# ``_fwd_kernel`` classifies a grid step from block indices: skipped (nothing
# computed, nothing fetched), fully visible (no mask) or crossed (the mask).
# Every case below makes all the kinds its mask can make in ONE call, at blocks
# of 128, and checks value and lse against materialised scores — and against
# the same kernel with every running tile sent through the mask.

def _forward_reference(q, k, v, *, scale, causal, window=None, kv_lens=None,
                       bias=None, second=None, rate=0.0, seed=None):
    """Head-major materialised scores in float32: q (b, h, sq, d), k / v
    (b, h, sk, ·) with kv heads repeated, ``kv_lens`` (b, h), ``bias``
    broadcastable to (b, h, sq, sk), ``second`` = (q2, k2) head-major with
    k2's heads repeated. Returns (o (b, h, sq, dv), lse (b, h, sq))."""
    from apex_tpu.ops.pallas.attention import NEG_INF, dropout_keep

    b, h, sq, _ = q.shape
    sk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k)
    if second is not None:
        s = s + jnp.einsum("bhqd,bhkd->bhqk", *second)
    s = s * scale
    if bias is not None:
        s = s + bias
    rows = jnp.arange(sq)[:, None] + (sk - sq)
    cols = jnp.arange(sk)[None, :]
    keep = jnp.ones((sq, sk), bool)
    if causal:
        keep = keep & (cols <= rows)
    if window is not None:
        keep = keep & (cols > rows - window)
    keep = jnp.broadcast_to(keep, s.shape)
    if kv_lens is not None:
        keep = keep & (cols < kv_lens[:, :, None, None])
    s = jnp.where(keep, s, NEG_INF)
    m = jnp.max(s, -1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, -1, keepdims=True)
    prob = p / l
    if rate:
        t = (jnp.arange(b)[:, None] * h + jnp.arange(h)[None, :])[:, :, None, None]
        kept = dropout_keep(seed, t, jnp.arange(sq)[:, None], cols, rate)
        prob = prob * jnp.where(kept, 1.0 / (1.0 - rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", prob, v), (m + jnp.log(l))[..., 0]


# wrapper, sq, sk, heads (h, h_kv), d, and what rides the call
_TILE_CASES = {
    # the flat layout: more keys than queries (off = 256: a q block's first two
    # tiles fully visible, one crossed, one skipped)
    "flat_causal_longer_keys": dict(form="flat", sq=256, sk=512, h=2, h_kv=2, d=32),
    "flat_causal_grouped": dict(form="flat", sq=512, sk=512, h=4, h_kv=2, d=32),
    # a length that ends INSIDE a tile the diagonal leaves fully visible
    # (row 0: 200 of 512, tile (3, 1) holds columns 128-255): the mask branch
    "flat_causal_lengths": dict(form="flat", sq=512, sk=512, h=2, h_kv=2, d=32,
                                lens=(200, 512, 129, 384)),
    "flat_full_lengths": dict(form="flat", sq=256, sk=512, h=2, h_kv=1, d=32, causal=False,
                              lens=(200, 512, 129, 384)),
    "flat_causal_bias": dict(form="flat", sq=256, sk=512, h=2, h_kv=2, d=32, bias=2),
    "flat_causal_bucketed_bias": dict(form="flat", sq=512, sk=512, h=2, h_kv=2, d=32, rel=True),
    "flat_causal_dropout": dict(form="flat", sq=256, sk=512, h=2, h_kv=2, d=32, rate=0.3),
    "flat_full": dict(form="flat", sq=256, sk=384, h=2, h_kv=2, d=32, causal=False),
    # the packed projection buffer
    "packed_causal": dict(form="packed", sq=512, sk=512, h=4, h_kv=1, d=128),
    "packed_causal_lengths": dict(form="packed", sq=512, sk=512, h=4, h_kv=2, d=32,
                                  lens=(200, 385)),
    "packed_causal_bias_dropout": dict(form="packed", sq=512, sk=512, h=2, h_kv=2, d=32,
                                       bias=2, rate=0.2),
    # heads of 64 two to a lane tile
    "pair_causal": dict(form="packed", sq=512, sk=512, h=4, h_kv=4, d=64),
    "pair_causal_lengths_dropout": dict(form="packed", sq=512, sk=512, h=2, h_kv=2, d=64,
                                        lens=(200, 385), rate=0.2),
    # separate seq-major arrays
    "bshd_causal_longer_keys": dict(form="bshd", sq=256, sk=512, h=4, h_kv=2, d=128),
    "bshd_causal_lengths": dict(form="bshd", sq=512, sk=512, h=2, h_kv=1, d=128,
                                lens=(200, 385)),
    "bshd_causal_bias": dict(form="bshd", sq=256, sk=512, h=2, h_kv=2, d=128, bias=1),
    "bshd_causal_dropout": dict(form="bshd", sq=512, sk=512, h=2, h_kv=2, d=128, rate=0.3),
    # a window whose lower edge crosses a tile (300 of blocks of 128), longer
    # keys; one that is a whole number of tiles; one with a length
    "bshd_window_edge": dict(form="bshd", sq=384, sk=512, h=2, h_kv=1, d=128, window=300),
    "bshd_window_of_tiles": dict(form="bshd", sq=512, sk=512, h=2, h_kv=1, d=128, window=256),
    "bshd_window_lengths": dict(form="bshd", sq=512, sk=512, h=2, h_kv=1, d=128, window=300,
                                lens=(450, 512)),
    # two head widths and a second score term on one shared key
    "bshd_second": dict(form="bshd", sq=512, sk=512, h=4, h_kv=4, d=128, dv=128, d2=64),
    "bshd_second_wider_values": dict(form="bshd", sq=384, sk=384, h=2, h_kv=1, d=128, dv=256,
                                     d2=64),
}


class TestForwardTileKinds:
    @pytest.mark.pallas
    @pytest.mark.parametrize("case", sorted(_TILE_CASES))
    def test_value_and_lse_match_materialised_scores(self, case, monkeypatch):
        from apex_tpu.ops.pallas import attention as pk

        c = dict(_TILE_CASES[case])
        form, sq, sk, h, h_kv, d = (c[n] for n in ("form", "sq", "sk", "h", "h_kv", "d"))
        causal, window, rate = c.get("causal", True), c.get("window"), c.get("rate", 0.0)
        dv, d2, b, blk = c.get("dv", d), c.get("d2", 0), 2, 128
        key = jr.fold_in(K, 4600 + sorted(_TILE_CASES).index(case))
        n = lambda i, *shape: jr.normal(jr.fold_in(key, i), shape)  # noqa: E731
        q, k, v = n(0, b, h, sq, d), n(1, b, h_kv, sk, d), n(2, b, h_kv, sk, dv)
        second = (n(3, b, h, sq, d2), n(4, b, 1, sk, d2)) if d2 else None
        bias = n(5, c["bias"], sq, sk) if c.get("bias") else None
        seed = jnp.int32(91) if rate else None
        scale = (d + d2) ** -0.5
        rep = lambda x: jnp.repeat(x, h // x.shape[1], 1)  # noqa: E731
        # lengths a batch row (seq-major) or a (batch, head) row (flat)
        lens = None if "lens" not in c else jnp.array(c["lens"], jnp.int32)
        lens_bh = None if lens is None else (
            lens.reshape(b, h) if form == "flat" else jnp.repeat(lens[:, None], h, 1))
        kw = dict(scale=scale, causal=causal, kv_lens=lens, bq=blk, bk=blk, interpret=True,
                  dropout_rate=rate, dropout_seed=seed)
        rel = None
        if c.get("rel"):
            from apex_tpu.ops.attention import BucketedBias
            table = BucketedBias(n(6, 8, h) * 0.5, False, 64)
            bias_ref, rel = table.materialize(sq, sk)[None], table.kernel_operands()
        else:
            bias_ref = None if bias is None else jnp.tile(bias, (b * h // bias.shape[0], 1, 1)
                                                          ).reshape(b, h, sq, sk)
        seq_major = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731

        def run():
            if form == "flat":
                o, lse = pk.flash_fwd(q.reshape(b * h, sq, d), k.reshape(b * h_kv, sk, d),
                                      v.reshape(b * h_kv, sk, d), bias=bias, rel_bias=rel, **kw)
                return o.reshape(b, h, sq, d), lse.reshape(b, h, sq)
            if form == "packed":
                qkv = jnp.concatenate([seq_major(x).reshape(b, sq, -1) for x in (q, k, v)], -1)
                o, lse = pk.flash_fwd_packed(qkv, h, h_kv, d, bias=bias, **kw)
                return seq_major(o.reshape(b, sq, h, d)), lse
            o, lse = pk.flash_fwd_bshd(seq_major(q), seq_major(k), seq_major(v), bias=bias,
                                       window=window, second=second, **kw)
            return seq_major(o), lse

        # the call holds every kind of step its mask can make
        running, visible, skipped = pk.forward_tiles(sq, sk, blk, blk, causal, window)
        if causal:
            assert visible > 0 and running > visible and skipped > 0, (running, visible, skipped)
        with jax.default_matmul_precision("highest"):
            o, lse = run()
            o_ref, lse_ref = _forward_reference(
                q, rep(k), rep(v), scale=scale, causal=causal, window=window, kv_lens=lens_bh,
                bias=bias_ref, second=None if second is None else (second[0], rep(second[1])),
                rate=rate, seed=seed)
            # every running tile through the mask, as before there was a branch
            kind = pk._tile_kind
            monkeypatch.setattr(pk, "_tile_kind", lambda *a: (kind(*a)[0], False))
            o_masked, lse_masked = run()
        np.testing.assert_allclose(o, o_ref, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(lse, lse_ref, atol=2e-5, rtol=2e-5)
        # to a rounding: interpreted on the CPU the two branches are two XLA
        # fusions (one contracts scale and maximum into an fma); on the chip
        # the forms are held bit-equal to the parent's by tools/tpu_kernel_smoke.py
        np.testing.assert_allclose(o, o_masked, atol=1e-6, rtol=0)
        np.testing.assert_allclose(lse, lse_masked, atol=1e-6, rtol=0)

    @pytest.mark.pallas
    def test_a_call_with_nothing_to_mask_has_one_body_and_no_branch(self):
        """Not causal, no lengths: one unmasked body — no ``cond``, no iota."""
        from apex_tpu.ops.pallas import attention as pk

        q = jnp.zeros((2, 256, 32))
        text = lambda **kw: str(jax.make_jaxpr(lambda q: pk.flash_fwd(  # noqa: E731
            q, q, q, scale=1.0, bq=128, bk=128, interpret=True, **kw))(q))
        full, causal = text(causal=False), text(causal=True)
        assert "iota" not in full and "iota" in causal
        # init, finish / init, finish and the two kinds of running tile
        assert full.count("cond[") == 2 and causal.count("cond[") == 4
