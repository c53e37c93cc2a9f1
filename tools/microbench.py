"""Component microbenchmarks against plain-XLA baselines — the measurable
targets in BASELINE.md ("FusedAdam/FusedLAMB step time: beat unfused optax
on 1M-param MLP"; "FusedLayerNorm/RMSNorm + fused_dense block: beat
plain-XLA reference").

    python tools/microbench.py            # run on whatever backend is live

Prints one line per benchmark: name, framework time, baseline time, ratio.
Measured numbers are recorded in PERF.md.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import jax.random as jr


def timeit(step, carry, iters=64, repeats=3):
    """Per-iteration device time of ``step: carry -> carry`` via an
    on-device ``fori_loop`` and slope timing.

    Host-side timing of one call is useless for sub-ms kernels: the
    per-call dispatch and host fetch dwarf the kernel. And a loop whose
    iterations don't feed each other lets XLA hoist loop-invariant work and
    dead-code-eliminate everything but the one fetched element (optax.adam
    "measured" 0.000 ms that way). So: the benchmarked op must be a
    self-feeding carry update, ``fori_loop``-ed long enough (~1 s) that the
    single dispatch + scalar fetch is <1% of the span; the carry dependence
    forces every iteration to execute in full.
    """

    def run_time(n):
        @jax.jit
        def run(c):
            return jax.lax.fori_loop(0, n, lambda i, c: step(c), c)

        out = run(carry)
        float(jax.tree.leaves(out)[0].ravel()[0])  # fetch = real completion
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = run(carry)
            float(jax.tree.leaves(out)[0].ravel()[0])
            best = min(best, time.perf_counter() - t0)
        return best

    # pilot to size N for a ~1 s span, then one long measured run
    per = max(run_time(iters) / iters, 1e-7)
    n = int(min(max(1.0 / per, iters), 65536))
    return run_time(n) / n


def report(name, ours, base):
    print(f"{name:<38} ours {ours*1e3:8.3f} ms   baseline {base*1e3:8.3f} ms"
          f"   x{base/ours:.2f}")


def bench_fused_adam():
    """Chunked FusedAdam vs unfused optax.adam on a ~1M-param MLP pytree."""
    import optax

    from apex_tpu.optimizers import fused_adam

    key = jr.PRNGKey(0)
    # a realistic many-tensor pytree: 8 layers of (weight, bias)
    params = {}
    for i in range(8):
        k1, key = jr.split(key)
        params[f"w{i}"] = jr.normal(k1, (360, 360), jnp.float32)
        params[f"b{i}"] = jnp.zeros((360,), jnp.float32)

    ours_opt = fused_adam(learning_rate=1e-3)
    base_opt = optax.adam(1e-3)

    def bench(opt):
        # grads derive from the evolving params so every iteration does a
        # full, un-hoistable update
        def step(carry):
            params, state = carry
            grads = jax.tree.map(lambda x: x * 1e-3, params)
            updates, state = opt.update(grads, state, params)
            return optax.apply_updates(params, updates), state

        return timeit(step, (params, opt.init(params)))

    report("fused_adam vs optax.adam (1M params)", bench(ours_opt), bench(base_opt))


def bench_fused_lamb():
    import optax

    from apex_tpu.optimizers import fused_lamb

    key = jr.PRNGKey(1)
    params = {}
    for i in range(8):
        k1, key = jr.split(key)
        params[f"w{i}"] = jr.normal(k1, (360, 360), jnp.float32)
        params[f"b{i}"] = jnp.zeros((360,), jnp.float32)

    def bench(opt):
        def step(carry):
            params, state = carry
            grads = jax.tree.map(lambda x: x * 1e-3, params)
            updates, state = opt.update(grads, state, params)
            return optax.apply_updates(params, updates), state

        return timeit(step, (params, opt.init(params)))

    report("fused_lamb vs optax lamb (1M params)",
           bench(fused_lamb(learning_rate=1e-3)),
           bench(optax.lamb(1e-3)))


def bench_layer_norm():
    """Pallas LN fwd+bwd vs jnp composition, transformer-shaped input."""
    from apex_tpu.ops.layer_norm import fused_layer_norm

    x = jr.normal(jr.PRNGKey(2), (16 * 1024, 1024), jnp.bfloat16)
    g = jnp.ones((1024,), jnp.bfloat16)
    b = jnp.zeros((1024,), jnp.bfloat16)

    def ours_loss(x, g, b):
        return jnp.sum(fused_layer_norm(x, g, b).astype(jnp.float32))

    def base_loss(x, g, b):
        xf = x.astype(jnp.float32)
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5) * g.astype(jnp.float32) + b.astype(jnp.float32)
        return jnp.sum(y)

    def bench(loss):
        gfn = jax.grad(loss, argnums=(0, 1, 2))

        def step(carry):
            # thread ALL inputs through the carry: dgamma/dbeta must be
            # consumed or XLA DCEs them (asymmetrically — an opaque Pallas
            # bwd can't be partially eliminated)
            x_, g_, b_ = carry
            gx, gg, gb = gfn(x_, g_, b_)
            return x_ - 1e-6 * gx, g_ - 1e-6 * gg, b_ - 1e-6 * gb

        return timeit(step, (x, g, b))

    report("fused layer_norm fwd+bwd (16k x 1024)",
           bench(ours_loss), bench(base_loss))


def bench_fused_dense_gelu_dense():
    """DenseGeluDense block vs naive chained jnp ops."""
    from apex_tpu.ops.fused_dense import fused_dense_gelu_dense

    H, F = 1024, 4096
    x = jr.normal(jr.PRNGKey(3), (16 * 128, H), jnp.bfloat16)
    # torch (out_features, in_features) convention, matching the module
    w1 = jr.normal(jr.PRNGKey(4), (F, H), jnp.bfloat16) * 0.02
    b1 = jnp.zeros((F,), jnp.bfloat16)
    w2 = jr.normal(jr.PRNGKey(5), (H, F), jnp.bfloat16) * 0.02
    b2 = jnp.zeros((H,), jnp.bfloat16)

    def ours_loss(x, w1, b1, w2, b2):
        return jnp.sum(fused_dense_gelu_dense(x, w1, b1, w2, b2).astype(jnp.float32))

    def base_loss(x, w1, b1, w2, b2):
        h = jax.nn.gelu(x @ w1.T + b1)
        return jnp.sum((h @ w2.T + b2).astype(jnp.float32))

    def bench(loss):
        gfn = jax.grad(loss, argnums=(0, 1, 2, 3, 4))

        def step(carry):
            x_, w1_, b1_, w2_, b2_ = carry
            gs = gfn(x_, w1_, b1_, w2_, b2_)
            return tuple(c - 1e-6 * g for c, g in zip(carry, gs))

        return timeit(step, (x, w1, b1, w2, b2))

    report("dense_gelu_dense fwd+bwd (2k x 1024x4096)",
           bench(ours_loss), bench(base_loss))


def bench_softmax_xentropy():
    """Fused softmax-CE vs naive log_softmax + gather (32k vocab)."""
    from apex_tpu.ops.xentropy import softmax_cross_entropy_loss

    logits = jr.normal(jr.PRNGKey(6), (8 * 1024, 32768), jnp.float32)
    labels = jr.randint(jr.PRNGKey(7), (8 * 1024,), 0, 32768)

    def ours_loss(logits, labels):
        return jnp.mean(softmax_cross_entropy_loss(logits, labels))

    def base_loss(logits, labels):
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))

    def bench(loss):
        gfn = jax.grad(loss)

        def step(lg):
            return lg - 1e-3 * gfn(lg, labels)

        return timeit(step, logits)

    report("softmax_xentropy fwd+bwd (8k x 32768)",
           bench(ours_loss), bench(base_loss))


def bench_multihead_attn():
    """SelfMultiheadAttn fwd+bwd vs the stock per-projection + materialized
    softmax composition — the analog of the reference's
    ``contrib/examples/multihead_attn/perf_test_multihead_attn.py``
    (seq 1024, embed 1024, 16 heads — beyond fmha's 512 cap)."""
    from apex_tpu.contrib.multihead_attn import SelfMultiheadAttn

    E, H, B, S = 1024, 16, 8, 1024
    m = SelfMultiheadAttn(embed_dim=E, num_heads=H, dropout=0.0, bias=True)
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                          m.init(jr.PRNGKey(8)))
    x = jr.normal(jr.PRNGKey(9), (B, S, E), jnp.bfloat16)

    def ours_loss(p, x):
        return jnp.sum(m(p, x, causal=True, is_training=False)
                       .astype(jnp.float32))

    def base_loss(p, x):
        qkv = x @ p["qkv_weight"].T + p["qkv_bias"]
        q, k, v = jnp.split(qkv, 3, -1)
        d = E // H

        def heads(t):
            return t.reshape(B, S, H, d).transpose(0, 2, 1, 3)

        q, k, v = heads(q), heads(k), heads(v)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / d ** 0.5
        mask = jnp.tril(jnp.ones((S, S), bool))
        probs = jax.nn.softmax(jnp.where(mask, s, -1e30), -1).astype(x.dtype)
        o = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        o = o.transpose(0, 2, 1, 3).reshape(B, S, E)
        return jnp.sum((o @ p["out_weight"].T + p["out_bias"])
                       .astype(jnp.float32))

    def bench(loss):
        gfn = jax.grad(loss)

        def step(p):
            g = gfn(p, x)
            return jax.tree.map(lambda a, b: a - 1e-6 * b, p, g)

        return timeit(step, params, iters=16)

    report("self_multihead_attn fwd+bwd (8x1024)",
           bench(ours_loss), bench(base_loss))


def main():
    os.environ.setdefault("APEX_TPU_PALLAS", "1")
    print(f"backend: {jax.default_backend()} "
          f"({jax.devices()[0].device_kind})")
    bench_fused_adam()
    bench_fused_lamb()
    bench_layer_norm()
    bench_fused_dense_gelu_dense()
    bench_softmax_xentropy()
    bench_multihead_attn()


if __name__ == "__main__":
    main()
