"""Flagship benchmark: GPT training-step throughput + MFU on one chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "mfu": ...}

The measured config is a GPT-medium-class decoder (hidden 1024 x 12 layers,
seq 1024, batch 20, bf16 compute) doing a full train step (loss + grad +
FusedAdam update). ``vs_baseline`` compares the framework path (flash
attention with recompute-in-backward, fused norm/softmax kernel family,
fused optimizer) against the same model written the stock-JAX way: naive
attention (materialized scores, jnp softmax, probs saved by autodiff) and
unfused optax adam — the TPU analog of the reference's "apex vs stock
PyTorch" pitch (the reference publishes no numbers of its own, SURVEY.md
§6). ``mfu`` uses the PaLM-style analytic model-FLOPs count
(6N + 12*L*S*H per token) against the chip's peak bf16 FLOP/s — the table
shared with ``apex_tpu.monitor.report``, so the report CLI derives the
same MFU from the same convention.

With ``APEX_TPU_MONITOR=<path>`` the bench additionally streams monitor
telemetry (a ``meta`` record + one ``step`` record per timed fused pass,
emitted AFTER each pass's clock stops) and ``python -m apex_tpu.monitor
report <path>`` reproduces the tokens/s headline from them. The printed
result object is schema-validated before printing (no nan can ship
inside a success artifact).

``python bench.py --decode`` runs the SERVING leg instead
(:func:`decode_main`): KV-cached decode tokens/s/chip, prefill latency,
and the ratio against the naive recompute-the-prefix baseline, emitted
as one ``decode`` monitor record (explicit ``SKIP(reason)`` off-TPU).

``python bench.py --serve`` runs the CONTINUOUS-BATCHING serving leg
(:func:`serve_main`): a SEEDED offered-load sweep (Poisson arrivals,
mixed lengths, shared system prompts, a pool sized below worst case)
through the paged ``apex_tpu.serving.ServingEngine`` — copy-on-write
prefix caching, optimistic admission + evict-and-recompute preemption,
SLO-aware dispatch — measuring p50/p99 per-token latency, TTFT split by
prefix hit vs miss, tokens/s under churn, occupancy, preemption and
recompute counts — as one ``serve`` monitor record with greedy-parity
(no-churn AND across-the-sweep ``churn_parity`` including evicted and
prefix-hit requests) and jit-cache-pinned witnesses vs the
single-request engine (explicit ``SKIP(reason)`` off-TPU).
Request-level telemetry rides along: streaming-histogram quantiles,
per-request ``serve_event`` lifecycle records (now incl. the ``evict``
trail), periodic ``serve_window`` SLO records, and the
``serve_anomaly`` section (stragglers, queue buildup, SLO burn, pool
leaks — refcount-aware: a warm prefix cache is not a leak).

``python bench.py --longseq-bias`` runs the long-sequence relative-bias
leg (:func:`longseq_bias_main`): in-kernel BUCKETED bias vs the
materialized (h, s, s) operand — tokens/s + HBM high-water, one
``longseq_bias`` monitor record (same SKIP semantics).

``python bench.py --tp-overlap`` runs the tensor-parallel overlap leg
(:func:`tp_overlap_main`): the ring-overlapped boundary collectives
(``GPTConfig(tp_overlap=True)`` → ``ops.collective_matmul``) vs the
blocking oracle, fwd+bwd tokens/s at tp >= 2 — one ``tp_overlap``
monitor record (``OK`` only on real multichip TPU; off-TPU the leg runs
at smoke scale on the virtual 8-device CPU mesh and the record is an
explicit ``SKIP(reason)``).

``python bench.py --pipeline`` runs the pipeline-schedule leg
(:func:`pipeline_main`): the zero-bubble split-backward schedule
(``GPTConfig(pp_schedule="zb")``) vs the autodiff 1f1b baseline through
``GPTPipeline`` at pp >= 2 — tokens/s for both, bubble % measured by
``step_anatomy`` on TPU and from the trace-time unit-cost geometry
everywhere, and a recompile-free witness across schedule-geometry
reuse — as one ``pipeline`` monitor record (same SKIP semantics).

``python bench.py --ckpt`` runs the elastic-checkpoint leg
(:func:`ckpt_main`): a ZeRO-sharded GPT train loop under
``apex_tpu.ckpt.ZeroCheckpointManager`` async saves — clean vs saving
step time (``save_overhead_pct``, the series ``tools/bench_history.py``
gates lower-is-better), snapshot/write/commit split, plus the bitwise
same-dp and elastic dp-resize resume witnesses measured in-process —
as one ``ckpt`` monitor record (same SKIP semantics off-TPU).

``python bench.py --spec`` runs the speculative-decoding +
quantized-KV leg (:func:`spec_main`): greedy generation with an n-gram
drafter vs the plain decode loop — tokens/s/request at batch 1 AND
under scheduler churn (``ServingEngine.serve(draft=...)``), the
acceptance rate, the greedy/churn parity witnesses, and the int8 KV
pool's teacher-forced logit error vs the float oracle — as one CLOSED
``spec`` monitor record (``tools/bench_history.py`` gates
``spec_tokens_per_s_request`` and the acceptance-rate series
higher-is-better; same SKIP semantics off-TPU). ``--spec --tree`` adds
the tree-speculation leg (:func:`_spec_tree_leg`): fused tree verify at
batch 1 and under churn with the drafter's KV in the SHARED paged pool,
peak drafter pool blocks, and the adaptive-vs-fixed (depth, branching)
witness on a recorded bimodal acceptance trace — the ``tree_spec_*``
series gate the same way.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import jax.random as jr

# the spec-peak table lives in apex_tpu.monitor.report — one table shared
# by this artifact and `python -m apex_tpu.monitor report`, so "mfu" means
# the same thing everywhere
from apex_tpu import monitor
from apex_tpu.monitor import trace as monitor_trace


def model_flops_per_token(cfg, seq):
    """PaLM-convention train-step FLOPs/token: 6*N_matmul + 12*L*S*H.

    N_matmul = per-layer matmul params (qkv 3H^2 + out H^2 + up 4H^2 +
    down 4H^2 = 12H^2) * L + tied unembedding V*H. Embedding lookup is a
    gather (0 FLOPs); LN/bias terms are negligible.
    """
    H, L, V = cfg["hidden_size"], cfg["num_layers"], cfg["vocab_size"]
    n_matmul = 12 * L * H * H + V * H
    return 6 * n_matmul + 12 * L * seq * H


def build(impl: str, cfg_kwargs, donate: bool):
    import optax

    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.optimizers import fused_adam

    if impl == "baseline":
        # the stock-JAX formulation: naive attention and whole-block
        # jax.checkpoint (remat stays ON here — the naive path's saved probs
        # blow 16G HBM by layer 3 without it; the framework path runs
        # un-rematted, which is itself framework value: the flash kernel's
        # O(s) residuals and the CE's recompute-from-lse backward are what
        # make that fit)
        cfg_kwargs = dict(cfg_kwargs, attention_impl="naive", remat=True,
                          remat_policy="full")
    cfg = GPTConfig(**cfg_kwargs)
    model = GPTModel(cfg)
    params = model.init(jr.PRNGKey(0))
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)

    if impl == "fused":
        opt = fused_adam(learning_rate=1e-4)
    else:
        opt = optax.adam(1e-4)
    opt_state = opt.init(params)

    def train_step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(model.loss_fn)(params, tokens, targets)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    jit_kwargs = {"donate_argnums": (0, 1)} if donate else {}
    return jax.jit(train_step, **jit_kwargs), params, opt_state


def timeit(step, params, opt_state, tokens, targets, iters, passes=3,
           return_passes=False, monitor_tokens=None):
    """Min over ``passes`` timed loops (min-of-3, VERDICT r4 next #7),
    applied to BOTH impls so vs_baseline stays symmetric. ``return_passes``
    additionally returns the raw per-pass times so the shipped artifact
    carries its own noise bar (spread = (max-min)/min across passes).
    Donated buffers chain through the pass loop, so one call is safe under
    donation; do NOT reuse the caller's params/opt_state after it.

    ``monitor_tokens`` (tokens per iteration) additionally emits one
    monitor ``step`` record per timed pass — AFTER the pass's clock stops,
    so telemetry adds zero time inside the measured window (the <1%
    monitoring-overhead budget is enforced by construction)."""
    params, opt_state, loss = step(params, opt_state, tokens, targets)  # compile+warm
    float(loss)  # host fetch: waits for the warm-up step
    times = []
    last_loss = None
    for _ in range(passes):
        t0 = time.perf_counter()
        for _ in range(iters):
            params, opt_state, loss = step(params, opt_state, tokens, targets)
        last_loss = float(loss)  # forces completion of the whole dependent chain
        times.append((time.perf_counter() - t0) / iters)
        if monitor_tokens is not None and monitor.enabled():
            monitor.begin_step()
            monitor.end_step(dur_s=times[-1], tokens=monitor_tokens,
                             loss=last_loss, iters=iters)
    best = min(times)
    if return_passes:
        return best, times
    return best


def decode_main():
    """``python bench.py --decode`` — the serving leg: KV-cached decode
    tokens/s/chip + prefill latency through ``apex_tpu.inference``,
    measured against the naive recompute-the-prefix formulation (the
    O(s²)-per-token path a repo without a KV cache is stuck with).

    Emits ONE ``decode`` record through the monitor schema (and onto the
    ``APEX_TPU_MONITOR`` stream when enabled) and prints it as one JSON
    line. On TPU the record is ``status: "OK"`` with the naive baseline
    and the cached/naive ratio; off-TPU it is an explicit
    ``status: "SKIP"`` with a reason — the smoke-scale CPU measurements
    still ride along as finite numbers, but a SKIP record claims no
    serving result (the honesty rule: never nan inside an OK artifact).
    The headline is min-of-passes with ``spread_pct`` as the noise bar,
    the same accounting as the training bench."""
    on_tpu = jax.default_backend() == "tpu"
    monitor.enable_from_env()
    from apex_tpu.inference import DecodeEngine
    from apex_tpu.models import GPTConfig, GPTModel

    if on_tpu:
        # the flagship train-bench config (head_dim 128 — same MXU-lane
        # reasoning); batch 16 holds a 2·12·16·8·1024·128 bf16 cache
        # (~800 MB) comfortably next to the bf16 params
        cfg = dict(vocab_size=32768, max_seq_len=1024, hidden_size=1024,
                   num_layers=12, num_heads=8, tp_size=1, remat=False,
                   attention_impl="flash", scan_layers=False)
        batch, prompt_len, new_tokens, passes = 16, 512, 128, 3
        naive_tokens = 16  # O(s²)/token: a short honest sample suffices
        cast = jnp.bfloat16
    else:  # smoke scale; the record is SKIP either way
        cfg = dict(vocab_size=256, max_seq_len=128, hidden_size=64,
                   num_layers=2, num_heads=4, tp_size=1, remat=False,
                   attention_impl="flash")
        batch, prompt_len, new_tokens, passes = 2, 32, 16, 2
        naive_tokens = 8
        cast = None

    model = GPTModel(GPTConfig(**cfg))
    params = model.init(jr.PRNGKey(0))
    if cast is not None:
        params = jax.tree.map(lambda x: x.astype(cast), params)
    engine = DecodeEngine(model, cache_dtype=cast)
    prompt = jr.randint(jr.PRNGKey(1), (batch, prompt_len), 0,
                        cfg["vocab_size"])
    key = jr.PRNGKey(2)

    # compile+warm both steps, then time: prefill passes first
    cache, tok, _ = engine.prefill(params, prompt, key)
    cache, tok, _ = engine.decode_step(params, cache, tok,
                                       jnp.int32(prompt_len), key)
    jax.block_until_ready(tok)
    pre_times = []
    for _ in range(passes):
        t0 = time.perf_counter()
        cache, tok, _ = engine.prefill(params, prompt, key)
        jax.block_until_ready(tok)
        pre_times.append(time.perf_counter() - t0)
    prefill_ms = min(pre_times) * 1e3

    # decode passes: each decodes new_tokens from a fresh prefill; only
    # the decode loop is inside the clock
    times = []
    for _ in range(passes):
        cache, tok, _ = engine.prefill(params, prompt, key)
        jax.block_until_ready(tok)
        t0 = time.perf_counter()
        for t in range(new_tokens):
            cache, tok, _ = engine.decode_step(
                params, cache, tok, jnp.int32(prompt_len + t), key)
        jax.block_until_ready(tok)
        times.append(time.perf_counter() - t0)
    tokens_per_s = batch * new_tokens / min(times)
    spread = (max(times) - min(times)) / min(times)
    # the zero-recompile contract is part of what is being measured: a
    # re-trace inside the timed loop would be dispatch overhead, not decode
    assert engine.decode_step._cache_size() == 1, \
        "decode_step re-traced during the bench (unstable avals?)"

    fields = dict(
        tokens_per_s=round(tokens_per_s, 1),
        prefill_ms=round(prefill_ms, 2),
        spread_pct=round(spread * 100, 2),
        batch=batch, prompt_len=prompt_len, new_tokens=new_tokens,
        max_seq_len=cfg["max_seq_len"],
        pass_times_ms=[round(t * 1e3, 2) for t in times],
        config=cfg, backend=jax.default_backend(),
    )

    if on_tpu:
        # naive recompute baseline: full forward over the whole prefix per
        # token — what serving WITHOUT the cache costs
        S = prompt_len + naive_tokens

        def naive_step(params, seq, pos):
            logits = model.logits(params, seq)
            last = jax.lax.dynamic_slice_in_dim(
                logits, pos - 1, 1, axis=1)[:, 0]
            nxt = jnp.argmax(last, -1).astype(seq.dtype)
            return jax.lax.dynamic_update_slice(
                seq, nxt[:, None], (jnp.int32(0), pos)), nxt

        naive = jax.jit(naive_step, donate_argnums=(1,))
        seq0 = jnp.zeros((batch, S), prompt.dtype).at[:, :prompt_len].set(
            prompt)
        seq, _ = naive(params, seq0, jnp.int32(prompt_len))  # compile+warm
        jax.block_until_ready(seq)
        ntimes = []
        for _ in range(passes):
            seq = jnp.zeros((batch, S), prompt.dtype
                            ).at[:, :prompt_len].set(prompt)
            jax.block_until_ready(seq)
            t0 = time.perf_counter()
            for t in range(naive_tokens):
                seq, nxt = naive(params, seq, jnp.int32(prompt_len + t))
            jax.block_until_ready(nxt)
            ntimes.append(time.perf_counter() - t0)
        naive_tps = batch * naive_tokens / min(ntimes)
        fields.update(naive_tokens_per_s=round(naive_tps, 1),
                      vs_naive=round(tokens_per_s / naive_tps, 4))
        status = "OK"
    else:
        reason = (f"decode serving throughput is a TPU measurement; this "
                  f"is a {jax.default_backend()} smoke run")
        fields.update(
            naive_tokens_per_s=("skipped", reason),
            vs_naive=("skipped", reason),
            reason=reason)
        status = "SKIP"

    if monitor.enabled():
        record = monitor.get_registry().emit_decode(status, **fields)
    else:  # sink-less registry: same construction+honesty path, no file
        record = monitor.MetricsRegistry().emit_decode(status, **fields)
    errors = monitor.validate(record)
    if errors:
        raise ValueError(f"decode bench record failed validation: {errors}")
    print(json.dumps(record))


#: seed of the serve sweep's Poisson trace — a fixed, recorded constant
#: so every sweep is replayable (the `trace_seed` field in the record)
SERVE_TRACE_SEED = 0


def build_serve_trace(seed, n_req, offered_rps, vocab, prompt_rng,
                      newtok_rng, sys_prompt_len=0, n_sys_prompts=2,
                      share_frac=0.5):
    """The serve sweep's request trace, fully determined by ``seed``:
    Poisson arrivals at ``offered_rps``, mixed prompt/output lengths,
    and — when ``sys_prompt_len > 0`` — a ``share_frac`` fraction of
    requests prefixed with one of ``n_sys_prompts`` shared system
    prompts (the chat/agent workload the prefix cache exists for).
    Same seed → token-identical requests and arrival times: sweeps are
    replayable (pinned by ``tests/test_serving.py``)."""
    import numpy as np

    from apex_tpu.serving import Request

    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / offered_rps, n_req))
    sys_prompts = [
        rng.integers(0, vocab, sys_prompt_len).astype(np.int32)
        for _ in range(n_sys_prompts)
    ] if sys_prompt_len > 0 else []
    requests = []
    for i in range(n_req):
        tail = rng.integers(
            0, vocab,
            int(rng.integers(prompt_rng[0],
                             prompt_rng[1] + 1))).astype(np.int32)
        if sys_prompts and rng.random() < share_frac:
            sysp = sys_prompts[int(rng.integers(len(sys_prompts)))]
            prompt = np.concatenate([sysp, tail])
        else:
            # same TOTAL length distribution as the shared population —
            # a fresh random prefix instead of a shared one, so the
            # hit-vs-miss TTFT split measures the cache, not a
            # prompt-length skew
            pad = rng.integers(0, vocab,
                               sys_prompt_len).astype(np.int32)
            prompt = np.concatenate([pad, tail]) if sys_prompt_len \
                else tail
        requests.append(Request(
            rid=i, prompt=prompt,
            max_new_tokens=int(rng.integers(newtok_rng[0],
                                            newtok_rng[1] + 1)),
            arrival_s=float(arrivals[i])))
    return requests


def serve_main():
    """``python bench.py --serve`` — the continuous-batching serving leg:
    an offered-load sweep (seeded Poisson arrivals, mixed prompt/output
    lengths, shared system prompts) through
    :class:`apex_tpu.serving.ServingEngine` — paged KV blocks with
    copy-on-write prefix caching, optimistic admission + preemption,
    chunked prefill, SLO-aware dispatch, fused sampling tail — measuring
    p50/p99 per-token latency, TTFT split by prefix-cache hit vs miss,
    decode tokens/s/chip under churn, and slot occupancy, plus the
    witnesses: greedy tokens IDENTICAL to the single-request
    ``DecodeEngine`` both with no churn AND across the sweep including
    evicted-and-recomputed and prefix-hit requests (``churn_parity``),
    with both jitted steps' cache size pinned at 1 across the whole
    hit/miss/evict/readmit schedule.

    The pool is deliberately sized BELOW worst-case-everything and the
    offered load runs 4x the tier-1 sweep (64 rps vs the 16 the PR-7
    leg drove): exhaustion must engage preemption (bounded p99, the
    ``evict`` lifecycle trail) instead of stalling admission.

    Emits ONE ``serve`` record through the monitor schema (and onto the
    ``APEX_TPU_MONITOR`` stream when enabled) and prints it as one JSON
    line. On TPU the record is ``status: "OK"``; off-TPU it is an
    explicit ``status: "SKIP"`` with a reason — the smoke-scale CPU
    measurements ride along as finite numbers, but a SKIP record claims
    no serving result (the honesty rule: never nan inside an OK
    artifact).

    Request-level telemetry (ISSUE 10) rides the churn sweep: a
    :class:`apex_tpu.serving.ServeTelemetry` feeds bounded-memory
    streaming histograms (replacing the r7 host sample lists), emits
    per-request ``serve_event`` lifecycle records and periodic
    ``serve_window`` SLO records onto the monitor stream, and the final
    record carries the ``serve_anomaly`` section, admission-pressure
    counts, prefix-cache/preemption fields, and the MEASURED telemetry
    overhead (``telemetry_overhead_pct`` — the <1%-of-a-serve-step
    budget, reported rather than assumed)."""
    import numpy as np

    on_tpu = jax.default_backend() == "tpu"
    monitor.enable_from_env()
    from apex_tpu.inference import DecodeEngine
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.serving import Request, ServeTelemetry, ServingEngine

    if on_tpu:
        # the flagship decode-bench config; 8 slots x 1024 rows of bf16
        # paged cache; the pool is sized to ~60% of worst-case-
        # everything so the 4x offered load actually exercises
        # preemption (the point of serving tier 2)
        cfg = dict(vocab_size=32768, max_seq_len=1024, hidden_size=1024,
                   num_layers=12, num_heads=8, tp_size=1, remat=False,
                   attention_impl="flash", scan_layers=False)
        slots, block, chunk = 8, 128, 256
        n_req, offered_rps = 64, 64.0   # 4x the PR-7 sweep's 16 rps
        num_blocks = 41                 # 40 allocatable of 64 worst-case
        prompt_rng, newtok_rng = (64, 512), (16, 128)
        sys_prompt_len = 256            # 2 shared full blocks
        parity_prompt, parity_new = 512, 64
        n_parity = 6
        cast = jnp.bfloat16
    else:  # smoke scale; the record is SKIP either way
        cfg = dict(vocab_size=256, max_seq_len=128, hidden_size=64,
                   num_layers=2, num_heads=4, tp_size=1, remat=False,
                   attention_impl="flash")
        slots, block, chunk = 2, 16, 32
        n_req, offered_rps = 8, 2000.0
        num_blocks = 9                  # 8 allocatable of 16 worst-case
        prompt_rng, newtok_rng = (4, 40), (2, 10)
        sys_prompt_len = 32             # 2 shared full blocks
        parity_prompt, parity_new = 16, 8
        n_parity = 8
        cast = None

    model = GPTModel(GPTConfig(**cfg))
    params = model.init(jr.PRNGKey(0))
    if cast is not None:
        params = jax.tree.map(lambda x: x.astype(cast), params)
    engine = ServingEngine(model, num_slots=slots, block_size=block,
                           prefill_chunk=chunk, num_blocks=num_blocks,
                           cache_dtype=cast)

    # --- no-churn witnesses: one greedy request, both engines ---------------
    deng = DecodeEngine(model, cache_dtype=cast)
    prompt = np.asarray(jr.randint(jr.PRNGKey(1), (parity_prompt,), 0,
                                   cfg["vocab_size"]), np.int32)
    # first passes compile both stacks AND witness greedy parity; the
    # second, warm passes below carry the throughput ratio
    want = deng.generate(params, jnp.asarray(prompt)[None], parity_new)
    jax.block_until_ready(want)
    # rid -1 is reserved for engine-level telemetry events; the two
    # warmup/parity requests take ids far above the sweep's, and all
    # warm/timed runs pass telemetry=False — the auto-attached tracker
    # would bill emit costs to the paged side of vs_single_request that
    # the DecodeEngine baseline does not pay, and its windows would
    # double-count against the sweep's serve_windows field
    done = engine.serve(params, [Request(rid=1_000_000, prompt=prompt,
                                         max_new_tokens=parity_new)],
                        telemetry=False)
    greedy_parity = (np.asarray(done[0].tokens)
                     == np.asarray(want)[0]).all()
    t0 = time.perf_counter()
    want = deng.generate(params, jnp.asarray(prompt)[None], parity_new)
    jax.block_until_ready(want)
    single_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    engine.serve(params, [Request(rid=1_000_001, prompt=prompt,
                                  max_new_tokens=parity_new)],
                 telemetry=False)
    paged_s = time.perf_counter() - t0
    single_tps = parity_new / single_s
    vs_single = (parity_new / paged_s) / single_tps

    # --- the churn sweep: seeded Poisson arrivals, mixed lengths, ------------
    # shared system prompts (the prefix-cache workload). Same seed →
    # identical trace: the sweep is replayable.
    requests = build_serve_trace(
        SERVE_TRACE_SEED, n_req, offered_rps, cfg["vocab_size"],
        prompt_rng, newtok_rng, sys_prompt_len=sys_prompt_len)
    # the telemetry layer: streaming histograms (bounded memory — the
    # r7 per-token host lists are gone from this aggregation), lifecycle
    # + window records on the monitor stream, anomaly detection. The
    # claim its window records carry matches the final record's.
    skip_reason = (None if on_tpu else
                   f"continuous-batching latency/throughput is a TPU "
                   f"measurement; this is a {jax.default_backend()} "
                   f"smoke run at {n_req} requests")
    tel = ServeTelemetry(
        slots=slots, window_s=0.25 if on_tpu else 0.01,
        slo_ttft_ms=1000.0 if on_tpu else 10000.0,
        status="OK" if on_tpu else "SKIP", reason=skip_reason,
        # keep the raw lifecycle ledger in memory: the per-request TTFT
        # attribution below consumes it whether or not a JSONL sink is
        # attached
        collect_events=True)
    sched = engine.make_scheduler()
    t0 = time.perf_counter()
    done = engine.serve(params, requests, scheduler=sched, telemetry=tel)
    wall = time.perf_counter() - t0
    assert len(done) == n_req, "serve lost requests"
    stats = engine.last_stats

    total_tokens = sum(len(r.tokens) for r in done)
    # the zero-recompile contract IS part of what is measured: any
    # re-trace across this hit/miss/evict/readmit churn schedule would
    # be dispatch overhead — and it must hold WITH telemetry attached
    # (lifecycle records are emitted outside the jitted steps)
    jit_cache_ok = (engine.prefill_chunk._cache_size() == 1
                    and engine.decode_step._cache_size() == 1)
    assert jit_cache_ok, \
        "serving steps re-traced under churn (unstable avals?)"
    # pool accounting must be refcount-exact after the sweep: no leak,
    # and every live block a cache-resident (warm prefix, not demand)
    sched.allocator.check_accounting()
    assert sched.allocator.num_live == sched.allocator.num_resident, \
        "blocks live beyond the prefix cache's residents after drain"

    # greedy parity ACROSS the churn sweep, prioritizing the requests
    # the tier-2 machinery touched: evicted-and-recomputed streams and
    # prefix-cache hits must be token-identical to the unpreempted,
    # uncached DecodeEngine baseline (capped: each distinct prompt
    # length compiles one baseline prefill)
    touched = [r for r in done
               if r.evictions > 0 or r.prefix_hit_blocks > 0]
    untouched = [r for r in done if not (r.evictions > 0
                                         or r.prefix_hit_blocks > 0)]
    checked = (touched + untouched)[:n_parity]
    churn_parity = True
    for r in checked:
        want = np.asarray(deng.generate(
            params, jnp.asarray(r.prompt)[None], r.max_new_tokens))[0]
        ok = (len(r.tokens) == r.max_new_tokens
              and (np.asarray(r.tokens) == want).all())
        churn_parity = churn_parity and bool(ok)

    fields = dict(
        tokens_per_s=round(total_tokens / wall, 1),
        # streaming-histogram quantiles (parity with the removed
        # sample-list math within one bucket width — pinned by
        # tests/test_histogram.py) + the tier-2 prefix/preemption view
        **tel.final_fields(sched.allocator, sched),
        telemetry_overhead_pct=round(100.0 * tel.overhead_s / wall, 4),
        occupancy_pct=round(stats.occupancy_pct(slots), 2),
        vs_single_request=round(vs_single, 4),
        single_request_tokens_per_s=round(single_tps, 1),
        offered_rps=offered_rps,
        greedy_parity=bool(greedy_parity),
        churn_parity=bool(churn_parity),
        churn_parity_checked=len(checked),
        jit_cache_ok=bool(jit_cache_ok),
        trace_seed=SERVE_TRACE_SEED,
        requests=n_req, slots=slots, block_size=block,
        num_blocks=engine.num_blocks,
        blocks_high_water=stats.blocks_high_water,
        prefill_chunk=chunk,
        decode_steps=stats.decode_steps,
        prefill_chunks=stats.prefill_chunks,
        max_seq_len=engine.max_s,
        config=cfg, backend=jax.default_backend(),
    )
    if on_tpu:
        status = "OK"
    else:
        fields["reason"] = skip_reason
        status = "SKIP"

    if monitor.enabled():
        record = monitor.get_registry().emit_serve(status, **fields)
    else:  # sink-less registry: same construction+honesty path, no file
        record = monitor.MetricsRegistry().emit_serve(status, **fields)
    errors = monitor.validate(record)
    if errors:
        raise ValueError(f"serve bench record failed validation: {errors}")
    print(json.dumps(record))

    # --- per-request TTFT/latency attribution over the same sweep ------------
    # decompose every finished request's e2e latency into queue /
    # prefill / decode / spec / preempt / swap components from the
    # telemetry ledger (collect_events=True above — no sink needed) and
    # ship the summary as a second record; status mirrors the serve
    # record's (a SKIP sweep prices nothing)
    attr = monitor_trace.serve_attribution(tel.events, per_request=False)
    if status == "SKIP":
        attr.setdefault("reason", skip_reason)
    if monitor.enabled():
        record = monitor.get_registry().emit_serve_attribution(
            status, **attr)
    else:
        record = monitor.MetricsRegistry().emit_serve_attribution(
            status, **attr)
    errors = monitor.validate(record)
    if errors:
        raise ValueError(
            f"serve_attribution record failed validation: {errors}")
    print(json.dumps(record))


def build_load_shift_trace(seed, n_calm, calm_rps, n_burst, burst_rps,
                           vocab, prompt_rng, newtok_rng,
                           sys_prompt_len=0, gap_s=0.05):
    """A two-regime serve trace, fully determined by ``seed``: a CALM
    segment at ``calm_rps`` followed (after ``gap_s``) by a BURST
    segment at ``burst_rps`` — the load shift the online
    :class:`~apex_tpu.serving.ReplanPolicy` exists for. Rids are
    contiguous across the two segments; same seed → token-identical
    trace (the replay fixture ``tests/test_serve_plan.py`` prices)."""
    from apex_tpu.serving import Request

    calm = build_serve_trace(seed, n_calm, calm_rps, vocab, prompt_rng,
                             newtok_rng, sys_prompt_len=sys_prompt_len)
    burst = build_serve_trace(seed + 1, n_burst, burst_rps, vocab,
                              prompt_rng, newtok_rng,
                              sys_prompt_len=sys_prompt_len)
    offset = (calm[-1].arrival_s if calm else 0.0) + gap_s
    shifted = [Request(rid=n_calm + i, prompt=r.prompt,
                       max_new_tokens=r.max_new_tokens,
                       arrival_s=float(r.arrival_s) + offset)
               for i, r in enumerate(burst)]
    return calm + shifted


def plan_serve_main(argv=None):
    """``python bench.py --serve --plan-serve [--costdb F]`` — the
    serving-plan leg (ISSUE 20): search → pick → measure → re-plan, the
    ``--plan`` discipline applied to the SERVING knobs.

    **Search**: record the seeded load-shift trace
    (:func:`build_load_shift_trace`), serve it once under the HAND
    config to calibrate the replay model's per-phase costs from the
    telemetry ledger (chunk-prefill ms/token, per-dispatch decode ms —
    the PR-16 attribution terms; CostDB rates + conservative floors
    cover the rest, every unpriced term a flagged ``uncalibrated`` key,
    never silently defaulted), then replay the trace through the
    host-side discrete-event model for every candidate on the grid
    (:func:`apex_tpu.plan.serve.search_serve_plans`) and rank by
    predicted tokens/s.

    **Pick + measure**: the searched winner is served on the SAME
    recorded trace and its measured tokens/s lands next to the
    prediction — ``predicted_vs_measured_err_pct`` is the honesty
    series ``tools/bench_history.py`` gates (absolute points), and
    ``searched_beats_hand`` witnesses the headline: the searched plan
    beats the hand config on the recorded trace (tokens/s AND TTFT
    p50, compared on the bit-deterministic replay pricing — the same
    model both plans are priced by).

    **Re-plan**: the trace is served a third time under a
    :class:`~apex_tpu.serving.ReplanPolicy` two-plan ladder (calm →
    loaded, aval-stable diffs only); the burst must trigger at least
    one live mid-serve switch (``replans``), greedy output must stay
    token-identical across it (``replan_parity``), and both jit caches
    stay pinned at 1 (``jit_cache_ok``) — the zero-recompile contract
    IS part of what is measured.

    Emits ONE schema-validated ``serve_plan`` record. On TPU it is
    ``status: "OK"``; off-TPU an explicit ``SKIP`` with a reason — the
    measured half rides as explicit skip objects (never nan in an OK
    line) with ``smoke_tokens_per_s`` as the finite plumbing witness."""
    import sys

    import numpy as np

    on_tpu = jax.default_backend() == "tpu"
    monitor.enable_from_env()
    from apex_tpu.inference import DecodeEngine
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.plan import (
        ServePlan,
        conservative_defaults,
        derive_serve_costs,
        price_serve_plan,
        search_serve_plans,
        serve_plan_record_fields,
    )
    from apex_tpu.prof.calibrate import validate_costdb
    from apex_tpu.serving import ReplanPolicy, ServeTelemetry, ServingEngine

    argv = list(sys.argv[1:] if argv is None else argv)
    costdb_path = (argv[argv.index("--costdb") + 1]
                   if "--costdb" in argv else None)

    if on_tpu:
        # the serve_main flagship config as the HAND plan: the baseline
        # the search must beat on its own recorded trace
        cfg = dict(vocab_size=32768, max_seq_len=1024, hidden_size=1024,
                   num_layers=12, num_heads=8, tp_size=1, remat=False,
                   attention_impl="flash", scan_layers=False)
        hand = ServePlan(num_blocks=41, block_size=128, num_slots=8,
                         prefill_chunk=256, max_prefill_share=4,
                         slo_ttft_ms=1000.0)
        n_calm, calm_rps, n_burst, burst_rps = 16, 16.0, 48, 128.0
        prompt_rng, newtok_rng = (64, 512), (16, 128)
        sys_prompt_len, window_s = 256, 0.25
        n_parity = 6
        cast = jnp.bfloat16
    else:  # smoke scale; the record is SKIP either way
        cfg = dict(vocab_size=256, max_seq_len=128, hidden_size=64,
                   num_layers=2, num_heads=4, tp_size=1, remat=False,
                   attention_impl="flash")
        hand = ServePlan(num_blocks=9, block_size=16, num_slots=2,
                         prefill_chunk=32, max_prefill_share=4,
                         slo_ttft_ms=10000.0)
        # calm trickle then a burst arriving faster than 2 slots drain:
        # the ~60 ms arrival ramp spans several 10 ms windows, so the
        # queue grows monotonically across at least three of them — the
        # buildup detector MUST fire and the ladder MUST step up
        n_calm, calm_rps, n_burst, burst_rps = 4, 40.0, 24, 400.0
        prompt_rng, newtok_rng = (4, 40), (2, 10)
        sys_prompt_len, window_s = 32, 0.01
        n_parity = 4
        cast = None

    model = GPTModel(GPTConfig(**cfg))
    params = model.init(jr.PRNGKey(0))
    if cast is not None:
        params = jax.tree.map(lambda x: x.astype(cast), params)
    requests = build_load_shift_trace(
        SERVE_TRACE_SEED, n_calm, calm_rps, n_burst, burst_rps,
        cfg["vocab_size"], prompt_rng, newtok_rng,
        sys_prompt_len=sys_prompt_len)
    n_req = len(requests)
    skip_reason = (None if on_tpu else
                   f"serving-plan throughput/TTFT is a TPU measurement; "
                   f"this is a {jax.default_backend()} smoke run at "
                   f"{n_req} requests")

    def _measured_serve(plan, policy=None):
        """Serve the recorded trace under ``plan``: (tokens/s, TTFT
        p50 ms, telemetry, engine, done results, wall s)."""
        from apex_tpu.serving import Request

        eng = ServingEngine(model, cache_dtype=cast,
                            **plan.engine_kwargs())
        # warm both jitted steps BEFORE the timed trace: a cold compile
        # inside the sweep would stall the serve clock past every
        # arrival and poison both the measured costs and the window
        # telemetry the re-planner keys on (rid far above the sweep's)
        warm_prompt = np.asarray(jr.randint(
            jr.PRNGKey(2), (plan.prefill_chunk,), 0,
            cfg["vocab_size"]), np.int32)
        eng.serve(params, [Request(rid=1_000_000, prompt=warm_prompt,
                                   max_new_tokens=4)], telemetry=False)
        tel = ServeTelemetry(
            slots=plan.num_slots, window_s=window_s,
            status="OK" if on_tpu else "SKIP", reason=skip_reason,
            collect_events=True, **plan.telemetry_kwargs())
        sched = eng.make_scheduler(policy=policy)
        # Request objects carry their RESULT fields (tokens, stamps):
        # each replay leg serves fresh copies of the recorded trace
        replay = [Request(rid=r.rid, prompt=r.prompt,
                          max_new_tokens=r.max_new_tokens,
                          arrival_s=r.arrival_s) for r in requests]
        t0 = time.perf_counter()
        done = eng.serve(params, replay, scheduler=sched, telemetry=tel)
        wall = time.perf_counter() - t0
        assert len(done) == n_req, "serve lost requests"
        tokens = sum(len(r.tokens) for r in done)
        ttfts = sorted(e["ttft_ms"] for e in tel.events
                       if e.get("phase") == "first_token")
        p50 = (ttfts[max(0, -(-len(ttfts) // 2) - 1)] if ttfts
               else float("nan"))
        return tokens / wall, p50, tel, eng, done, wall

    # --- calibrate: the hand-config serve is the measured-cost source --------
    hand_tps, hand_p50, tel, eng, done, wall = _measured_serve(hand)
    stats = eng.last_stats
    prefill_ms = sum(e.get("prefill_ms") or 0.0 for e in tel.events
                     if e.get("phase") == "first_token")
    live_prefill = sum(
        max(len(r.prompt) - r.prefix_hit_blocks * hand.block_size, 1)
        for r in done)
    measured = dict(
        prefill_ms_per_token=max(prefill_ms / max(live_prefill, 1), 1e-6),
        # per-DISPATCH cost at the hand config's average live width (the
        # per-row split stays a CostDB term); on the deliberately
        # overloaded trace the non-prefill wall is decode-dominated
        decode_ms_per_step=max(
            (wall * 1e3 - prefill_ms) / max(stats.decode_steps, 1), 1e-6),
    )

    if costdb_path:
        with open(costdb_path) as fh:
            db = json.load(fh)
        errors = validate_costdb(db)
        if errors:
            raise ValueError(f"{costdb_path} is not a valid costdb: "
                             f"{errors}")
        source = costdb_path
    else:
        # no measured CostDB: every db-priced key is a flagged blind
        # spot at the conservative floors — labeled, never silent
        db = {"schema": 1, "kind": "costdb", "collectives": {},
              "gemms": {}}
        source = "uniform-reference"
    costs = derive_serve_costs(
        db, hidden_size=cfg["hidden_size"], num_layers=cfg["num_layers"],
        num_heads=cfg["num_heads"], vocab_size=cfg["vocab_size"],
        measured=measured, **conservative_defaults(db))

    # --- search the grid on the recorded trace -------------------------------
    result = search_serve_plans(requests, costs, base=hand)
    best = result.best
    hand_price = price_serve_plan(hand, requests, costs)
    # the headline comparison, on the SAME bit-deterministic replay
    # pricing both plans ride (predicted↔measured drift is gated
    # separately via predicted_vs_measured_err_pct)
    beats = (best.price.predicted_tokens_per_s
             > hand_price.predicted_tokens_per_s
             and best.price.predicted_ttft_p50_ms
             <= hand_price.predicted_ttft_p50_ms)

    # --- measure the searched winner on the same trace -----------------------
    best_tps, best_p50, _tel2, eng2, _done2, _w2 = _measured_serve(
        best.plan)

    # --- live re-plan under the load shift -----------------------------------
    # a calm → loaded ladder over the SEARCHED plan's aval geometry:
    # only aval-stable knobs differ (share bound, admission, SLO), so
    # every switch applies live and the jit caches must stay at 1
    calm_plan = best.plan
    loaded_plan = ServePlan(**{
        **calm_plan.to_json(),
        "max_prefill_share": max(calm_plan.max_prefill_share, 4),
        "admission": "short_first",
        "slo_ttft_ms": None,
    })
    policy = ReplanPolicy(plans=(calm_plan, loaded_plan))
    rp_tps, _rp_p50, tel3, eng3, done3, _w3 = _measured_serve(
        calm_plan, policy=policy)
    jit_cache_ok = (eng3.prefill_chunk._cache_size() == 1
                    and eng3.decode_step._cache_size() == 1)
    assert jit_cache_ok, \
        "re-planned serving steps re-traced (unstable avals?)"
    assert tel3.replans >= 1 and policy.replans == tel3.replans, \
        "the load shift produced no live re-plan (buildup never fired?)"
    # greedy parity ACROSS the switch: finished streams token-identical
    # to the unchurned DecodeEngine baseline
    deng = DecodeEngine(model, cache_dtype=cast)
    replan_parity = True
    for r in done3[:n_parity]:
        want = np.asarray(deng.generate(
            params, jnp.asarray(r.prompt)[None], r.max_new_tokens))[0]
        ok = (len(r.tokens) == r.max_new_tokens
              and (np.asarray(r.tokens) == want).all())
        replan_parity = replan_parity and bool(ok)

    fields = serve_plan_record_fields(
        result, costdb_source=source,
        measured_tokens_per_s=best_tps if on_tpu else None,
        measured_ttft_p50_ms=best_p50 if on_tpu else None,
        skip_reason=skip_reason)
    skip = lambda r: ("skipped", r)  # noqa: E731
    fields.update(
        hand_tokens_per_s=(round(hand_tps, 1) if on_tpu
                           else skip(skip_reason)),
        hand_ttft_p50_ms=(round(hand_p50, 3) if on_tpu
                          else skip(skip_reason)),
        searched_beats_hand=bool(beats),
        replans=int(tel3.replans),
        replan_parity=bool(replan_parity),
        jit_cache_ok=bool(jit_cache_ok),
        smoke_tokens_per_s=round(best_tps, 1),
        trace_seed=SERVE_TRACE_SEED,
        config=cfg, backend=jax.default_backend(),
    )
    if on_tpu:
        status = "OK"
    else:
        fields["reason"] = skip_reason
        status = "SKIP"

    if monitor.enabled():
        record = monitor.get_registry().emit_serve_plan(status, **fields)
    else:  # sink-less registry: same construction+honesty path, no file
        record = monitor.MetricsRegistry().emit_serve_plan(status,
                                                           **fields)
    errors = monitor.validate(record)
    if errors:
        raise ValueError(
            f"serve_plan bench record failed validation: {errors}")
    print(json.dumps(record))


def tp_serve_main(argv):
    """``python bench.py --serve --plan-tp N`` — the tensor-parallel
    serving leg (ISSUE 17): serve a model bigger than one chip.

    * **Sharded churn sweep**: the seeded serve trace through a
      ``plan=ParallelPlan(tp=N)`` :class:`~apex_tpu.serving.
      ServingEngine` — paged KV pool sharded over kv_heads, QKV/output
      projections riding the ring-overlap collective matmuls, the
      sampling tail psum-composed — vs the tp=1 engine on the SAME
      trace, with the greedy whole-sweep token-parity witness and both
      jit caches pinned at 1.
    * **Collective traffic**: the decode step's ``ppermute`` ring
      calls/bytes from the :func:`~apex_tpu.monitor.hooks.
      count_collective` counters the rings bump at trace time (one
      trace == one step's traffic under the pinned-cache contract).
    * **Disaggregated prefill→decode**: a prefill-role engine serves
      the requests to first token (its TTFT stands alone), the KV
      chains stream through :mod:`apex_tpu.serving.disagg` (manifest +
      sha256 block digests across a directory boundary), a decode-role
      engine ingests and finishes them — output token-identical to the
      monolithic run (``handoff_parity``), transfer bytes/blocks/wall
      in the record, ``handoff`` lifecycle events carrying one
      trace_id across both roles.

    Emits ONE schema-validated ``tp_serve`` record (CLOSED — junk keys
    fail) and prints it as one JSON line. ``status: "OK"`` only on a
    real TPU with >= N chips; anywhere else (CPU virtual mesh, too few
    chips) the record is an explicit ``status: "SKIP"`` with a reason —
    the smoke measurements ride along as finite numbers, never nan in
    an OK line."""
    import tempfile

    import numpy as np

    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.plan.parallel_plan import ParallelPlan
    from apex_tpu.serving import (Request, ServeTelemetry, ServingEngine,
                                  export_handoff, ingest_handoff,
                                  prefill_requests, read_handoff,
                                  write_handoff)

    tp = 2
    if "--plan-tp" in argv:
        i = argv.index("--plan-tp")
        if i + 1 < len(argv):
            tp = int(argv[i + 1])
    monitor.enable_from_env()
    if not monitor.enabled():
        # memory-only registry: the ring-traffic counters (and the
        # record's construction+honesty path) need one even without a
        # JSONL sink attached
        monitor.enable()
    reg = monitor.get_registry()

    on_tpu = (jax.default_backend() == "tpu"
              and len(jax.devices()) >= tp)
    if on_tpu:
        cfg = dict(vocab_size=32768, max_seq_len=1024, hidden_size=1024,
                   num_layers=12, num_heads=8, tp_size=1, remat=False,
                   attention_impl="flash", scan_layers=False)
        slots, block, chunk = 8, 128, 256
        n_req, offered_rps = 64, 64.0
        num_blocks = 65
        prompt_rng, newtok_rng = (64, 512), (16, 128)
        sys_prompt_len = 256
        hand_n, hand_prompt, hand_new = 6, (256, 512), (16, 64)
    else:
        cfg = dict(vocab_size=256, max_seq_len=128, hidden_size=64,
                   num_layers=2, num_heads=4, tp_size=1, remat=False,
                   attention_impl="flash")
        slots, block, chunk = 4, 16, 32
        n_req, offered_rps = 8, 2000.0
        num_blocks = 33
        prompt_rng, newtok_rng = (4, 40), (2, 10)
        sys_prompt_len = 32
        hand_n, hand_prompt, hand_new = 5, (18, 60), (4, 10)
    skip_reason = (
        None if jax.default_backend() == "tpu" and on_tpu else
        f"tp={tp} serving is a multichip-TPU measurement; this is a "
        f"{jax.default_backend()} run over "
        f"{min(tp, len(jax.devices()))} virtual-mesh devices"
        if jax.default_backend() != "tpu" else
        f"tp={tp} needs {tp} chips; this host has {len(jax.devices())}")

    model = GPTModel(GPTConfig(**cfg))
    params = model.init(jr.PRNGKey(0))

    def mk_engine(plan=None):
        return ServingEngine(model, num_slots=slots, block_size=block,
                             prefill_chunk=chunk, num_blocks=num_blocks,
                             plan=plan)

    trace_reqs = lambda: build_serve_trace(  # noqa: E731
        SERVE_TRACE_SEED, n_req, offered_rps, cfg["vocab_size"],
        prompt_rng, newtok_rng, sys_prompt_len=sys_prompt_len)

    # --- tp=1 baseline on the same trace ------------------------------------
    e1 = mk_engine()
    t0 = time.perf_counter()
    base = e1.serve(params, trace_reqs(), telemetry=False)
    base_wall = time.perf_counter() - t0
    base_toks = {r.rid: list(r.tokens) for r in base}
    base_tps = sum(len(r.tokens) for r in base) / base_wall

    # --- the sharded engine -------------------------------------------------
    plan = ParallelPlan(tp=tp)
    etp = mk_engine(plan)

    def ring_counters():
        return (int(reg.counters.get("collective/ppermute[tp]_calls", 0)),
                int(reg.counters.get("collective/ppermute[tp]_bytes", 0)))

    c0 = ring_counters()
    # one warm request first: the prefill program traces here, so the
    # counter delta across the main sweep isolates the decode trace —
    # under the pinned-cache contract one trace IS one step's traffic
    warm = etp.serve(params, [Request(rid=1_000_000,
                                      prompt=np.arange(block + 2,
                                                       dtype=np.int32),
                                      max_new_tokens=1)],
                     telemetry=False)
    assert len(warm) == 1
    c1 = ring_counters()
    tel = ServeTelemetry(
        slots=slots, window_s=0.25 if on_tpu else 0.01,
        slo_ttft_ms=1000.0 if on_tpu else 10000.0,
        status="OK" if on_tpu else "SKIP", reason=skip_reason,
        collect_events=True)
    t0 = time.perf_counter()
    done = etp.serve(params, trace_reqs(), telemetry=tel)
    tp_wall = time.perf_counter() - t0
    c2 = ring_counters()
    stats = etp.last_stats
    tp_toks = {r.rid: list(r.tokens) for r in done}
    greedy_parity = tp_toks == base_toks
    jit_cache_ok = (etp.prefill_chunk._cache_size() == 1
                    and etp.decode_step._cache_size() == 1)
    assert jit_cache_ok, \
        "tp serving steps re-traced under churn (unstable avals?)"
    ttft_mono = [1e3 * (r.first_token_s - r.submit_s) for r in done
                 if r.first_token_s is not None]
    # decode-step ring traffic: the sweep's trace-time delta (prefill
    # traced in the warm run above); zero means the decode trace
    # somehow ran early — report the conservative total then
    dec_calls, dec_bytes = c2[0] - c1[0], c2[1] - c1[1]
    tot_calls, tot_bytes = c2[0] - c0[0], c2[1] - c0[1]

    # --- disaggregated prefill -> decode handoff ----------------------------
    def hand_reqs():
        rng = np.random.default_rng(SERVE_TRACE_SEED + 17)
        return [Request(
            rid=i,
            prompt=rng.integers(0, cfg["vocab_size"],
                                int(rng.integers(*hand_prompt))
                                ).astype(np.int32),
            max_new_tokens=int(rng.integers(*hand_new)))
            for i in range(hand_n)]

    mono = mk_engine(plan).serve(params, hand_reqs(), telemetry=False)
    mono_toks = {r.rid: list(r.tokens) for r in mono}

    ep = mk_engine(plan)   # prefill role
    ed = mk_engine(plan)   # decode role (its own pool + scheduler)
    tel_hand = ServeTelemetry(slots=slots, status="OK" if on_tpu
                              else "SKIP", reason=skip_reason)
    sched_p = ep.make_scheduler()
    pre_done = ep.serve(params, prefill_requests(hand_reqs()),
                        scheduler=sched_p, telemetry=tel_hand)
    ttft_pre = [1e3 * (r.first_token_s - r.submit_s) for r in pre_done
                if r.first_token_s is not None]
    t0 = time.perf_counter()
    handoffs = [export_handoff(ep.last_pool, sched_p, r,
                               block_size=block, telemetry=tel_hand)
                for r in pre_done]
    with tempfile.TemporaryDirectory() as d:
        transfer_bytes = write_handoff(d, handoffs)
        streamed = read_handoff(d)   # digests verified per block here
    sched_d = ed.make_scheduler()
    pool_d, hstats = ingest_handoff(ed.init_pool(), sched_d, streamed,
                                    telemetry=tel_hand)
    transfer_ms = 1e3 * (time.perf_counter() - t0)
    dec_done = ed.serve(params, hand_reqs(), scheduler=sched_d,
                        pool=pool_d, telemetry=False)
    handoff_parity = ({r.rid: list(r.tokens) for r in dec_done}
                      == mono_toks)
    hit_all = all(r.prefix_hit_blocks > 0 for r in dec_done
                  if len(r.prompt) >= 2 * block)

    c = model.config
    row_bytes = (2 * c.num_layers * c.local_kv_heads * c.head_dim
                 * (2 if on_tpu else 4))
    pool_mb_total = num_blocks * block * row_bytes / 2 ** 20
    fields = dict(
        tp=tp,
        tokens_per_s=round(sum(len(r.tokens) for r in done) / tp_wall, 1),
        baseline_tokens_per_s=round(base_tps, 1),
        ttft_ms_prefill_role=round(float(np.mean(ttft_pre)), 3),
        ttft_ms_monolithic=round(float(np.mean(ttft_mono)), 3),
        handoff_blocks=hstats.blocks,
        handoff_transfer_bytes=transfer_bytes,
        handoff_transfer_ms=round(transfer_ms, 3),
        digests_verified=hstats.digests_verified,
        collective_ppermute_calls=tot_calls,
        collective_ppermute_bytes=tot_bytes,
        decode_steps=stats.decode_steps,
        collective_bytes_per_step=dec_bytes if dec_bytes else tot_bytes,
        greedy_parity=bool(greedy_parity),
        handoff_parity=bool(handoff_parity and hit_all
                            and hstats.skipped == 0),
        jit_cache_ok=bool(jit_cache_ok),
        kv_dtype="float",
        requests=n_req,
        num_blocks=num_blocks,
        pool_mb_per_shard=round(pool_mb_total / tp, 4),
        pool_mb_total=round(pool_mb_total, 4),
        config=cfg, backend=jax.default_backend(),
    )
    if on_tpu:
        status = "OK"
    else:
        fields["reason"] = skip_reason
        status = "SKIP"
    record = reg.emit_tp_serve(status, **fields)
    errors = monitor.validate(record)
    if errors:
        raise ValueError(
            f"tp_serve bench record failed validation: {errors}")
    print(json.dumps(record))


def spec_main(tree=False):
    """``python bench.py --spec`` — the speculative-decoding +
    quantized-KV leg (ROADMAP item 3, both factors of the decode-
    bandwidth attack in one artifact):

    * **Batch-1 speculation**: greedy generation through
      ``DecodeEngine.generate(draft=NGramDrafter(k))`` vs the plain
      decode loop — tokens/s/request both ways, the speedup ratio, the
      measured acceptance rate that explains it, and the greedy-parity
      witness (spec output token-identical to the baseline) with every
      jitted body's cache size pinned at 1.
    * **Speculation under churn**: the same comparison through
      ``ServingEngine.serve(draft=...)`` on a seeded multi-request
      trace — spec rounds interleaving with chunked prefill, block
      tables rewound to the accepted frontier each round — with the
      whole-sweep token parity witness (``churn_parity``).
    * **int8 KV quantization**: the ``kv_dtype="int8"`` pool vs the
      float parity oracle, decode logits TEACHER-FORCED through both
      on identical contexts so the reported ``kv_quant_logit_err`` is
      a per-position bound, not a divergence artifact; pool footprints
      for both ride along.

    With ``--tree`` the record additionally carries the TREE-speculation
    leg (:func:`_spec_tree_leg`): fused tree verify at batch 1 and under
    churn with the small-model drafter's KV in the SHARED paged pool,
    plus the adaptive-vs-fixed (depth, branching) witness on a recorded
    bimodal acceptance trace.

    Emits ONE schema-validated ``spec`` record (a CLOSED schema — junk
    keys fail) and prints it as one JSON line. On TPU the record is
    ``status: "OK"``; off-TPU it is an explicit ``status: "SKIP"`` with
    a reason — the smoke-scale measurements ride along as finite
    numbers, but a SKIP record claims no serving result. Never nan in
    an OK line."""
    import numpy as np

    on_tpu = jax.default_backend() == "tpu"
    monitor.enable_from_env()
    from apex_tpu.inference import DecodeEngine
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.serving import ServingEngine
    from apex_tpu.spec import NGramDrafter

    if on_tpu:
        # the flagship decode-bench config; k=4 drafted tokens per round
        cfg = dict(vocab_size=32768, max_seq_len=1024, hidden_size=1024,
                   num_layers=12, num_heads=8, tp_size=1, remat=False,
                   attention_impl="flash", scan_layers=False)
        prompt_len, new_tokens, passes, k = 512, 128, 3, 4
        slots, block, chunk, n_req = 4, 128, 128, 16
        quant_tokens = 32
        cast = jnp.bfloat16
    else:  # smoke scale; the record is SKIP either way
        cfg = dict(vocab_size=256, max_seq_len=256, hidden_size=64,
                   num_layers=2, num_heads=4, tp_size=1, remat=False,
                   attention_impl="flash")
        prompt_len, new_tokens, passes, k = 32, 16, 2, 4
        slots, block, chunk, n_req = 2, 16, 16, 6
        quant_tokens = 8
        cast = None

    model = GPTModel(GPTConfig(**cfg))
    params = model.init(jr.PRNGKey(0))
    if cast is not None:
        params = jax.tree.map(lambda x: x.astype(cast), params)
    # a self-similar prompt (a tiled pattern): speculation's payoff is
    # acceptance, and acceptance needs guessable continuations — this is
    # the honest analog of the code/chat traffic speculation targets
    pat = np.asarray(jr.randint(jr.PRNGKey(1), (max(prompt_len // 4, 1),),
                                0, cfg["vocab_size"]), np.int32)
    prompt = np.tile(pat, 4)[:prompt_len]
    deng = DecodeEngine(model, cache_dtype=cast)
    drafter = NGramDrafter(k=k)

    # compile + the parity witness; one "spec-" trace id spans both legs
    # (the generate calls reuse the ambient id, so their spans — and the
    # final spec record, stamped explicitly below — share it)
    spec_tid = monitor_trace.new_trace_id("spec")
    with monitor_trace.trace_context(spec_tid):
        want = np.asarray(deng.generate(params, jnp.asarray(prompt)[None],
                                        new_tokens))
        spec_out = np.asarray(deng.generate(
            params, jnp.asarray(prompt)[None], new_tokens, draft=drafter))
    greedy_parity = bool((spec_out == want).all())
    stats = deng.last_spec_stats
    jit_cache_ok = (deng.spec_verify_step._cache_size() == 1
                    and deng.decode_step._cache_size() == 1)

    # timed passes: min-of-passes headline, spread as the noise bar
    base_times, spec_times = [], []
    for _ in range(passes):
        t0 = time.perf_counter()
        out = deng.generate(params, jnp.asarray(prompt)[None], new_tokens)
        jax.block_until_ready(out)
        base_times.append(time.perf_counter() - t0)
    for _ in range(passes):
        t0 = time.perf_counter()
        out = deng.generate(params, jnp.asarray(prompt)[None], new_tokens,
                            draft=drafter)
        jax.block_until_ready(out)
        spec_times.append(time.perf_counter() - t0)
    tps_spec = new_tokens / min(spec_times)
    tps_base = new_tokens / min(base_times)
    spread = (max(spec_times) - min(spec_times)) / min(spec_times)

    # --- speculation under churn: the serving engine with spec rounds --------
    # the trace is seed-determined, so each run gets a FRESH but
    # token-identical request list (a served Request carries its output)
    def trace():
        return build_serve_trace(
            SERVE_TRACE_SEED, n_req, 2000.0, cfg["vocab_size"],
            (4, max(prompt_len // 2, 8)), (2, max(new_tokens // 2, 4)))

    base_eng = ServingEngine(model, num_slots=slots, block_size=block,
                             prefill_chunk=chunk, cache_dtype=cast)
    done = base_eng.serve(params, trace(), telemetry=False)
    base_tokens = {r.rid: list(r.tokens) for r in done}
    t0 = time.perf_counter()
    done = base_eng.serve(params, trace(), telemetry=False)
    churn_base_s = time.perf_counter() - t0
    spec_eng = ServingEngine(model, num_slots=slots, block_size=block,
                             prefill_chunk=chunk, cache_dtype=cast)
    done = spec_eng.serve(params, trace(), telemetry=False,
                          draft=NGramDrafter(k=k))
    churn_parity = all(list(r.tokens) == base_tokens[r.rid] for r in done)
    jit_cache_ok = (jit_cache_ok
                    and spec_eng.prefill_chunk._cache_size() == 1
                    and spec_eng.spec_step._cache_size() == 1
                    and spec_eng.decode_step._cache_size() <= 1)
    t0 = time.perf_counter()
    done = spec_eng.serve(params, trace(), telemetry=False,
                          draft=NGramDrafter(k=k))
    churn_spec_s = time.perf_counter() - t0
    total = sum(len(r.tokens) for r in done)
    tps_churn = total / churn_spec_s
    tps_churn_base = total / churn_base_s

    # --- int8 KV pool vs the float parity oracle -----------------------------
    kv_err, q_mb, o_mb = _spec_quant_err(
        model, params, prompt, quant_tokens, slots=1, block=block,
        chunk=chunk, cast=cast)

    # --- the --tree leg: fused tree verify + pooled drafter + adaptive k -----
    tree_fields = {}
    if tree:
        with monitor_trace.trace_context(spec_tid):
            tree_fields = _spec_tree_leg(
                model, params, deng, prompt, want, new_tokens, passes,
                cfg, slots=slots, block=block, chunk=chunk, cast=cast,
                trace=trace, base_tokens=base_tokens, tps_base=tps_base)

    fields = dict(
        tokens_per_s_request=round(tps_spec, 1),
        baseline_tokens_per_s_request=round(tps_base, 1),
        speedup=round(tps_spec / tps_base, 4),
        tokens_per_s_churn=round(tps_churn, 1),
        baseline_tokens_per_s_churn=round(tps_churn_base, 1),
        speedup_churn=round(tps_churn / tps_churn_base, 4),
        acceptance_rate=round(stats.acceptance_rate, 4),
        accepted_per_round=round(stats.accepted / stats.rounds, 3)
        if stats.rounds else 0.0,
        rounds=stats.rounds,
        draft_k=k, drafter="ngram",
        kv_dtype="int8",
        kv_quant_logit_err=round(kv_err, 5),
        kv_quant_pool_mb=round(q_mb, 3),
        kv_oracle_pool_mb=round(o_mb, 3),
        greedy_parity=greedy_parity,
        churn_parity=bool(churn_parity),
        jit_cache_ok=bool(jit_cache_ok),
        prompt_len=prompt_len, new_tokens=new_tokens, requests=n_req,
        spread_pct=round(spread * 100, 2),
        pass_times_ms=[round(t * 1e3, 2) for t in spec_times],
        config=cfg, backend=jax.default_backend(),
    )
    fields.update(tree_fields)
    assert greedy_parity and churn_parity, \
        "speculative decode diverged from the non-speculative baseline"
    assert jit_cache_ok, "a spec body re-traced (unstable avals?)"
    if on_tpu:
        status = "OK"
    else:
        fields["reason"] = (
            f"speculative-decode throughput is a TPU measurement; this "
            f"is a {jax.default_backend()} smoke run")
        status = "SKIP"

    if monitor.enabled():
        record = monitor.get_registry().emit_spec(status, trace_id=spec_tid,
                                                  **fields)
    else:  # sink-less registry: same construction+honesty path, no file
        record = monitor.MetricsRegistry().emit_spec(
            status, trace_id=spec_tid, **fields)
    errors = monitor.validate(record)
    if errors:
        raise ValueError(f"spec bench record failed validation: {errors}")
    print(json.dumps(record))


def _spec_quant_err(model, params, prompt, n_tokens, *, slots, block,
                    chunk, cast):
    """Max |Δlogit| between the int8 pool and the float parity oracle,
    TEACHER-FORCED: both engines decode the oracle's token stream on
    identical contexts, so the bound measures quantization, not
    divergence. Returns ``(max_err, int8_pool_mb, oracle_pool_mb)``."""
    import numpy as np

    from apex_tpu.serving import Request, ServingEngine

    prompt = np.asarray(prompt[:max(len(prompt) // 2, 4)], np.int32)
    key0 = jr.PRNGKey(0)

    def drive(engine, forced=None):
        sched = engine.make_scheduler(prefix_cache=False)
        sched.submit(Request(rid=0, prompt=prompt,
                             max_new_tokens=n_tokens))
        sched.admit(0.0)
        pool = engine.init_pool()
        while True:
            w = sched.next_prefill(0.0)
            if w is None:
                break
            pool, tok, _ = engine.prefill_chunk(
                params, pool, jnp.asarray(sched.tables.row(w.slot)),
                jnp.asarray(w.tokens), jnp.int32(w.start),
                jnp.int32(w.live), key0)
            sched.note_prefill(w, int(tok), 0.0)
        rows, toks_out = [], []
        for t in range(n_tokens - 1):
            batch = sched.decode_batch(0.0)
            if batch is None:
                break
            toks, lens = batch
            pool, sampled, logits = engine.decode_step(
                params, pool, jnp.asarray(sched.tables.asarray()),
                jnp.asarray(toks), jnp.asarray(lens), key0)
            sampled = np.asarray(sampled).copy()
            if forced is not None:  # teacher-force the oracle's stream
                sampled[0] = forced[t]
            rows.append(np.asarray(logits[0], np.float32))
            toks_out.append(int(sampled[0]))
            sched.note_decode(sampled, 0.0)
        return np.stack(rows), toks_out

    oracle = ServingEngine(model, num_slots=slots, block_size=block,
                           prefill_chunk=chunk, cache_dtype=cast)
    l_oracle, forced = drive(oracle)
    quant = ServingEngine(model, num_slots=slots, block_size=block,
                          prefill_chunk=chunk, cache_dtype=cast,
                          kv_dtype="int8")
    l_quant, _ = drive(quant, forced=forced)
    err = float(np.max(np.abs(l_quant - l_oracle)))
    return err, quant.pool_bytes() / 1e6, oracle.pool_bytes() / 1e6


def _spec_tree_leg(model, params, deng, prompt, want, new_tokens, passes,
                   cfg, *, slots, block, chunk, cast, trace, base_tokens,
                   tps_base):
    """The ``--tree`` extension of the spec leg, three witnesses:

    * **Batch-1 tree verify**: ``DecodeEngine.generate`` with an
      :class:`NGramTreeDrafter` vs the plain-decode output already in
      hand — greedy tree output must be TOKEN-IDENTICAL (the deepest-
      fully-accepted-path winner is exactly the greedy chain), with the
      tree-verify body's jit cache pinned at one entry.
    * **Churn with a pooled drafter**: the same seeded trace through
      ``serve(draft=PagedModelDrafter(...))`` — the drafter's KV blocks
      come from the scheduler's OWN allocator, so the sweep also
      witnesses peak drafter blocks in the shared pool.
    * **Adaptive vs fixed**: :func:`_tree_policy_sim` replays one
      recorded bimodal acceptance trace under the adaptive controller
      and under every fixed shape in its static set; adaptive must beat
      them all on emitted-tokens-per-modeled-cost.

    Returns the ``tree_*`` fields of the spec record."""
    import numpy as np

    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.serving import ServingEngine
    from apex_tpu.spec import (AdaptiveSpecController, NGramTreeDrafter,
                               PagedModelDrafter)

    depth, branching = 4, 2
    tree_out = np.asarray(deng.generate(
        params, jnp.asarray(prompt)[None], new_tokens,
        draft=NGramTreeDrafter(depth=depth, branching=branching)))
    tree_greedy_parity = bool((tree_out == want).all())
    tstats = deng.last_spec_stats
    cache_ok = deng.spec_tree_step._cache_size() == 1
    times = []
    for _ in range(passes):
        t0 = time.perf_counter()
        out = deng.generate(params, jnp.asarray(prompt)[None], new_tokens,
                            draft=NGramTreeDrafter(depth=depth,
                                                   branching=branching))
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    tps_tree = new_tokens / min(times)

    # churn with the drafter's KV as first-class paged-pool state: a
    # half-size draft model, its blocks drawn from the target pool
    dcfg = dict(cfg, hidden_size=max(cfg["hidden_size"] // 2, 32),
                num_layers=max(cfg["num_layers"] // 2, 1))
    dmodel = GPTModel(GPTConfig(**dcfg))
    dparams = dmodel.init(jr.PRNGKey(7))
    if cast is not None:
        dparams = jax.tree.map(lambda x: x.astype(cast), dparams)
    pdraft = PagedModelDrafter(dmodel, dparams, depth=depth,
                               branching=branching)
    teng = ServingEngine(model, num_slots=slots, block_size=block,
                         prefill_chunk=chunk, cache_dtype=cast)
    done = teng.serve(params, trace(), telemetry=False, draft=pdraft)
    tree_churn_parity = all(list(r.tokens) == base_tokens[r.rid]
                            for r in done)
    cache_ok = cache_ok and teng.spec_tree_step._cache_size() == 1
    t0 = time.perf_counter()
    done = teng.serve(params, trace(), telemetry=False, draft=pdraft)
    churn_s = time.perf_counter() - t0
    total = sum(len(r.tokens) for r in done)
    tree_rounds = teng.last_stats.tree_rounds

    # adaptive (depth, branching) vs EVERY fixed shape in the static
    # set, replayed on one recorded bimodal acceptance trace
    choices = ((1, 1), (2, 1), (4, 1), (4, 2))
    adaptive_eff = _tree_policy_sim(
        adaptive=AdaptiveSpecController(choices, window=3))
    fixed_eff = [_tree_policy_sim(fixed=c) for c in choices]
    beats = all(adaptive_eff > e for e in fixed_eff)

    assert tree_greedy_parity and tree_churn_parity, \
        "tree-speculative decode diverged from the plain-decode baseline"
    assert cache_ok, "a tree-verify body re-traced (unstable avals?)"
    assert beats, (
        f"adaptive (depth, branching) did not beat every fixed shape on "
        f"the recorded bimodal trace: adaptive={adaptive_eff:.4f} vs "
        f"fixed={[round(e, 4) for e in fixed_eff]}")
    return dict(
        tree_spec_tokens_per_s_request=round(tps_tree, 1),
        tree_spec_tokens_per_s_churn=round(total / churn_s, 1),
        tree_spec_acceptance_rate=round(tstats.acceptance_rate, 4),
        tree_speedup=round(tps_tree / tps_base, 4),
        tree_depth=depth, tree_branching=branching,
        tree_nodes=branching * depth,
        tree_rounds=int(tree_rounds),
        tree_greedy_parity=tree_greedy_parity,
        tree_churn_parity=bool(tree_churn_parity),
        drafter_pool_blocks=int(pdraft.peak_blocks),
        adaptive_efficiency=round(adaptive_eff, 4),
        fixed_k_efficiency=[round(e, 4) for e in fixed_eff],
        adaptive_beats_fixed=bool(beats),
    )


def _tree_policy_sim(*, adaptive=None, fixed=None, streams=8, tokens=64,
                     p_easy=0.9, p_hard=0.1, overhead_rows=8.0, seed=11):
    """Replay one RECORDED bimodal acceptance trace — half the streams
    easy (per-row acceptance ``p_easy``), half hard (``p_hard``), draws
    fixed by ``seed`` — under a (depth, branching) policy, and score
    emitted tokens per MODELED verify cost. A round costs its verify
    rows (``branching*depth + 1``) plus ``overhead_rows``, the weight-
    streaming floor a decode dispatch pays regardless of row count;
    that floor is what makes depth pay on easy streams while wasted
    rows still hurt on hard ones, so neither a fixed-shallow nor a
    fixed-deep shape can win both halves. Pass ``adaptive=`` (an
    :class:`~apex_tpu.spec.AdaptiveSpecController`, queried and fed per
    round exactly like the serve loop does) or ``fixed=(depth,
    branching)``. Returns ``emitted / cost``."""
    import numpy as np

    emitted_total, cost = 0, 0.0
    for s in range(streams):
        p = p_easy if s % 2 == 0 else p_hard
        srng = np.random.RandomState(seed * 1000 + s)
        got = 0
        while got < tokens:
            d, b = adaptive.choice(s) if adaptive is not None else fixed
            # level 0 hedges: the first accepted branch (if any)
            # continues as a chain — the DraftTree acceptance shape
            accepted = 0
            if (srng.random_sample(b) < p).any():
                accepted = 1
                for _ in range(d - 1):
                    if srng.random_sample() >= p:
                        break
                    accepted += 1
            got += accepted + 1  # + the verify round's bonus token
            emitted_total += accepted + 1
            cost += b * d + 1 + overhead_rows
            if adaptive is not None:
                adaptive.note_round(s, accepted, d)
        if adaptive is not None:
            adaptive.release(s)
    return emitted_total / cost


def longseq_bias_main():
    """``python bench.py --longseq-bias`` — the long-sequence relative-
    bias leg: fwd+bwd flash attention with the IN-KERNEL bucketed bias
    (the ``BucketedBias`` operand: O(buckets·h) bias memory) against the
    r5 MATERIALIZED (h, s, s) operand (O(h·s²) — 1.5 GB fp32 at the TPU
    shape below), measuring tokens/s and the HBM high-water of each.

    Emits ONE ``longseq_bias`` record through the monitor schema and
    prints it as one JSON line; on TPU the record is ``status: "OK"``
    with both legs and the ratio, off-TPU an explicit ``status: "SKIP"``
    with a reason (smoke-scale CPU numbers ride along as finite fields,
    but a SKIP record claims no result — never nan in an OK line). HBM
    high-water comes from ``device.memory_stats()['peak_bytes_in_use']``;
    the peak is monotone per process, so the bucketed leg runs FIRST (its
    peak is its own) and the materialized leg's peak is read after —
    exact for the bucketed leg, a floor for the materialized one (which
    only understates the collapse being measured)."""
    on_tpu = jax.default_backend() == "tpu"
    monitor.enable_from_env()
    from apex_tpu.ops.attention import BucketedBias, flash_attention

    if on_tpu:
        # T5-large-ish attention shape at long seq: the ISSUE's 1.6 GB
        # example (s=8192, h=6) with head_dim 128 (MXU lanes)
        b, s, h, d, nb, passes, iters = 1, 8192, 6, 128, 32, 3, 5
    else:  # smoke scale; the record is SKIP either way
        b, s, h, d, nb, passes, iters = 1, 256, 2, 64, 16, 2, 1
    causal = False  # the T5 ENCODER case (bidirectional buckets)
    maxd = 128

    key = jr.PRNGKey(0)
    q = jr.normal(key, (b, s, h, d), jnp.bfloat16)
    k = jr.normal(jr.fold_in(key, 1), (b, s, h, d), jnp.bfloat16)
    v = jr.normal(jr.fold_in(key, 2), (b, s, h, d), jnp.bfloat16)
    table = jr.normal(jr.fold_in(key, 3), (nb, h), jnp.float32) * 0.3

    def bucketed_step(q, k, v, t):
        o = flash_attention(q, k, v, causal=causal, layout="bshd",
                            bias=BucketedBias(t, True, maxd))
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def materialized_step(q, k, v, bias_arr):
        o = flash_attention(q, k, v, causal=causal, layout="bshd",
                            bias=bias_arr)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def time_leg(fn, *args):
        g = jax.jit(jax.grad(fn, argnums=(0, 1, 2, 3)))
        out = g(*args)  # compile+warm
        jax.block_until_ready(out)
        times = []
        for _ in range(passes):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = g(*args)
            jax.block_until_ready(out)
            times.append((time.perf_counter() - t0) / iters)
        return times

    def peak_mb():
        if not on_tpu:
            return None
        stats = jax.local_devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        return None if peak is None else round(peak / 2 ** 20, 1)

    # bucketed leg FIRST: the process peak after it is ITS high-water
    bt = time_leg(bucketed_step, q, k, v, table)
    peak_bucketed = peak_mb()
    # materialized baseline: the (h, s, s) fp32 array the r5 path fed the
    # kernels (built outside the timed loop, as the model did per stack)
    bias_arr = BucketedBias(table, True, maxd).materialize(s, s)
    jax.block_until_ready(bias_arr)
    mt = time_leg(materialized_step, q, k, v, bias_arr)
    peak_materialized = peak_mb()

    tokens_per_s = b * s / min(bt)
    tokens_mat = b * s / min(mt)
    spread = (max(bt) - min(bt)) / min(bt)
    skip = lambda r: ("skipped", r)  # noqa: E731
    fields = dict(
        tokens_per_s=round(tokens_per_s, 1),
        tokens_per_s_materialized=round(tokens_mat, 1),
        vs_materialized=round(tokens_per_s / tokens_mat, 4),
        bias_bytes=int(nb * h * 4),
        bias_bytes_materialized=int(h * s * s * 4),
        seq=s, batch=b, heads=h, head_dim=d, num_buckets=nb,
        causal=causal, spread_pct=round(spread * 100, 2),
        pass_times_ms=[round(t * 1e3, 2) for t in bt],
        backend=jax.default_backend(),
    )
    no_stats = "device memory_stats unavailable on this backend"
    fields["hbm_peak_mb"] = (peak_bucketed if peak_bucketed is not None
                             else skip(no_stats))
    fields["hbm_peak_materialized_mb"] = (
        peak_materialized if peak_materialized is not None
        else skip(no_stats))
    if on_tpu:
        status = "OK"
    else:
        reason = (f"long-seq bias HBM/throughput is a TPU measurement; "
                  f"this is a {jax.default_backend()} smoke run at s={s}")
        fields["reason"] = reason
        status = "SKIP"

    if monitor.enabled():
        record = monitor.get_registry().emit_longseq_bias(status, **fields)
    else:  # sink-less registry: same construction+honesty path, no file
        record = monitor.MetricsRegistry().emit_longseq_bias(
            status, **fields)
    errors = monitor.validate(record)
    if errors:
        raise ValueError(
            f"longseq-bias bench record failed validation: {errors}")
    print(json.dumps(record))


def tp_overlap_main():
    """``python bench.py --tp-overlap`` — overlapped vs blocking TP
    boundary collectives on the flagship GPT block stack: one jitted
    fwd+bwd (loss + grads + SP grad sync) per impl under ``shard_map``
    on a tp-only mesh, tokens/s from min-of-passes with ``spread_pct``
    as the noise bar (the training bench's accounting).

    Emits ONE ``tp_overlap`` record through the monitor schema and
    prints it as one JSON line. ``status: "OK"`` requires a real
    multichip TPU (the overlap claim is an ICI-latency measurement);
    off-TPU the leg still runs end to end at smoke scale on a virtual
    8-device CPU mesh — the dryrun harness's recipe, with the
    device-count flag set here BEFORE jax initializes its backend — and
    the record is an explicit ``SKIP(reason)`` with the smoke numbers
    riding along as finite fields. A host with fewer than 2 usable
    devices emits SKIP without measurements. Never nan in an OK line."""
    # must precede the first backend query: the CPU platform only grows
    # virtual devices if the flag is set pre-initialization
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")
    on_tpu = jax.default_backend() == "tpu"
    monitor.enable_from_env()
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.models.gpt import shard_params_for_tp
    from apex_tpu.parallel import mesh as mesh_lib

    n = jax.device_count()
    tp = 4 if n % 4 == 0 else (2 if n % 2 == 0 else 0)

    def emit(status, **fields):
        if monitor.enabled():
            record = monitor.get_registry().emit_tp_overlap(status, **fields)
        else:  # sink-less registry: same construction+honesty path
            record = monitor.MetricsRegistry().emit_tp_overlap(
                status, **fields)
        errors = monitor.validate(record)
        if errors:
            raise ValueError(
                f"tp-overlap bench record failed validation: {errors}")
        print(json.dumps(record))

    if tp < 2:
        reason = (f"tp overlap needs >= 2 devices on one axis; this "
                  f"{jax.default_backend()} host exposes {n}")
        emit("SKIP", reason=reason, backend=jax.default_backend())
        return

    if on_tpu:
        # flagship-block scale at tp: head_dim 128, SP on (the production
        # pairing — boundary collectives on every linear, fwd and bwd)
        kw = dict(vocab_size=32768, max_seq_len=1024, hidden_size=1024,
                  num_layers=12, num_heads=8, attention_impl="flash",
                  remat=False, scan_layers=False)
        batch, seq, iters, passes = 8, 1024, 10, 3
        cast = jnp.bfloat16
    else:  # smoke scale on the virtual mesh; the record is SKIP anyway
        kw = dict(vocab_size=128, max_seq_len=64, hidden_size=64,
                  num_layers=2, num_heads=4, attention_impl="flash")
        batch, seq, iters, passes = 2, 64, 2, 2
        cast = None

    cfg1 = GPTConfig(**kw, tp_size=1)
    params1 = GPTModel(cfg1).init(jr.PRNGKey(0))
    if cast is not None:
        params1 = jax.tree.map(lambda x: x.astype(cast), params1)
    sharded = shard_params_for_tp(params1, tp, cfg1)
    specs = jax.tree.map(lambda _: P("tp"), sharded)
    mesh = mesh_lib.make_mesh(tensor_model_parallel_size=tp,
                              devices=jax.devices()[:tp])
    toks = jr.randint(jr.PRNGKey(1), (batch, seq), 0, kw["vocab_size"])
    tgts = jr.randint(jr.PRNGKey(2), (batch, seq), 0, kw["vocab_size"])

    def measure(overlap):
        # the ParallelPlan spelling (ISSUE 12): one validated object
        # instead of three loose kwargs
        from apex_tpu.plan import ParallelPlan
        model = GPTModel(GPTConfig(**kw, plan=ParallelPlan(
            tp=tp, sequence_parallel=True, tp_overlap=overlap)))

        def run(p, t, g):
            loss, grads = jax.value_and_grad(model.loss_fn)(
                jax.tree.map(lambda x: x[0], p), t, g)
            grads = model.sp_grad_sync(grads)
            return loss, jax.tree.map(lambda x: x[None], grads)

        step = jax.jit(mesh_lib.shard_map(
            run, mesh=mesh, in_specs=(specs, P(), P()),
            out_specs=(P(), specs)))
        loss, grads = step(sharded, toks, tgts)  # compile+warm
        float(loss)
        times = []
        for _ in range(passes):
            t0 = time.perf_counter()
            for _ in range(iters):
                loss, grads = step(sharded, toks, tgts)
            float(loss)  # host fetch syncs the dependent chain
            times.append((time.perf_counter() - t0) / iters)
        return batch * seq / min(times), times

    tps_overlap, pass_times = measure(True)
    tps_blocking, pass_times_b = measure(False)
    # spread over BOTH runs: vs_blocking is a ratio, so noise in the
    # blocking denominator moves the claim exactly as much as noise in
    # the overlapped numerator
    spread = (max(pass_times) - min(pass_times)) / min(pass_times)
    spread_b = (max(pass_times_b) - min(pass_times_b)) / min(pass_times_b)

    fields = dict(
        tokens_per_s=round(tps_overlap, 1),
        tokens_per_s_blocking=round(tps_blocking, 1),
        vs_blocking=round(tps_overlap / tps_blocking, 4),
        tp=tp, batch=batch, seq=seq, sequence_parallel=True,
        spread_pct=round(spread * 100, 2),
        spread_pct_blocking=round(spread_b * 100, 2),
        pass_times_ms=[round(t * 1e3, 2) for t in pass_times],
        pass_times_blocking_ms=[round(t * 1e3, 2) for t in pass_times_b],
        config=kw, backend=jax.default_backend(),
    )
    if on_tpu:
        status = "OK"
    else:
        reason = (f"tp-overlap speedup is an ICI-latency measurement; "
                  f"this is a {jax.default_backend()} smoke run on a "
                  f"virtual {n}-device mesh (tp={tp})")
        fields["reason"] = reason
        status = "SKIP"
    emit(status, **fields)


def profile_main(argv=None):
    """``python bench.py --profile [--logdir D] [--costdb F]`` — the
    step-anatomy leg: run the flagship train step with fwd_bwd/optimizer
    spans under a ``jax.profiler`` capture, fuse the span stream with the
    device trace (``prof.trace_reader.step_anatomy``), write the merged
    host+device timeline and the calibrated CostDB artifact
    (``prof.calibrate``), and emit ONE ``profile`` monitor record.

    On TPU the chrome trace carries per-HLO device events, the anatomy
    percentages are real and the record is ``status: "OK"``; off-TPU the
    trace is host-only (no XLA Ops track), so the record is an explicit
    ``status: "SKIP"`` with the smoke wall-times riding along and every
    device-derived metric an explicit skip object — never nan in an OK
    line. Span/trace/anatomy/CostDB *math* is tier-1-tested on synthetic
    fixtures; this leg is the real-capture path."""
    import sys

    from apex_tpu.monitor import report as monitor_report

    argv = list(sys.argv[1:] if argv is None else argv)

    def _opt(flag, default):
        return argv[argv.index(flag) + 1] if flag in argv else default

    logdir = _opt("--logdir", "/tmp/apex_tpu_profile")
    os.makedirs(logdir, exist_ok=True)
    costdb_path = _opt("--costdb", os.path.join(logdir, "costdb.json"))

    on_tpu = jax.default_backend() == "tpu"
    # spans need a live registry at TRACE time (scope names bake into the
    # compiled program's op names); respect APEX_TPU_MONITOR, else stream
    # next to the trace
    reg = monitor.enable_from_env()
    if reg is None:
        stream_path = os.path.join(logdir, "events.jsonl")
        monitor.enable(stream_path)
    else:
        stream_path = os.environ["APEX_TPU_MONITOR"]

    import optax

    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.optimizers import fused_adam
    from apex_tpu.prof import calibrate, cost_analysis, trace
    from apex_tpu.prof import trace_reader

    if on_tpu:
        cfg = dict(vocab_size=32768, max_seq_len=1024, hidden_size=1024,
                   num_layers=12, num_heads=8, tp_size=1, remat=False,
                   attention_impl="flash", scan_layers=False)
        batch, seq, steps = 20, 1024, 5
        cast = jnp.bfloat16
    else:  # smoke scale; the record is SKIP either way (host-only trace)
        cfg = dict(vocab_size=256, max_seq_len=64, hidden_size=64,
                   num_layers=2, num_heads=4, tp_size=1, remat=False,
                   attention_impl="flash")
        batch, seq, steps = 2, 64, 3
        cast = None

    model = GPTModel(GPTConfig(**cfg))
    params = model.init(jr.PRNGKey(0))
    if cast is not None:
        params = jax.tree.map(lambda x: x.astype(cast), params)
    opt = fused_adam(learning_rate=1e-4)
    opt_state = opt.init(params)
    tokens = jr.randint(jr.PRNGKey(1), (batch, seq), 0, cfg["vocab_size"])
    targets = jr.randint(jr.PRNGKey(2), (batch, seq), 0, cfg["vocab_size"])

    def train_step(params, opt_state, tokens, targets):
        # traced spans: fwd_bwd / optimizer scope every HLO they cover —
        # the join key the anatomy table and CostDB calibration read back
        # out of the device trace
        with monitor.span("fwd_bwd"):
            loss, grads = jax.value_and_grad(model.loss_fn)(
                params, tokens, targets)
        with monitor.span("optimizer"):
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    step = jax.jit(train_step, donate_argnums=(0, 1))

    monitor.emit_meta(
        device_kind=jax.devices()[0].device_kind if on_tpu else "cpu",
        backend=jax.default_backend(),
        model_flops_per_token=model_flops_per_token(cfg, seq),
        batch=batch, seq=seq, config=cfg,
        metric="gpt_step_anatomy_profile",
    )
    # XLA's own prediction for the whole program — the costdb's
    # achieved-vs-predicted reference line. TPU only: the CPU backend
    # reports no optimal_seconds, so the smoke run would pay a second
    # full compile for a None
    pred = None
    if on_tpu:
        ca = cost_analysis(train_step, params, opt_state, tokens, targets)
        if ca.get("flops", 0) > 0 and ca.get("optimal_seconds", 0) > 0:
            pred = ca["flops"] / ca["optimal_seconds"]

    # compile+warm OUTSIDE the capture (scope names are program
    # properties; the capture only needs executions)
    params, opt_state, loss = step(params, opt_state, tokens, targets)
    float(loss)
    with trace(logdir):
        for i in range(steps):
            with monitor.span("step", step=i):
                params, opt_state, loss = step(params, opt_state, tokens,
                                               targets)
                float(loss)  # block INSIDE the span: wall time is honest

    records = monitor_report.read_records(open(stream_path))
    spans = [r for r in records if r.get("kind") == "span"]
    events = trace_reader.read_trace(logdir)
    rows = trace_reader.step_anatomy(spans, events)
    timeline_path = os.path.join(logdir, "merged_trace.json")
    trace_reader.write_merged_timeline(timeline_path, spans, events)
    db = calibrate.build_costdb(
        records, events,
        device_kind=jax.devices()[0].device_kind if on_tpu else "cpu",
        backend=jax.default_backend(), predicted_flops_per_s=pred)
    calibrate.write_costdb(costdb_path, db)

    walls = [s["dur_ns"] / 1e9 for s in
             trace_reader.host_step_spans(spans)]
    fields = dict(
        steps=len(walls), span_records=len(spans),
        step_wall_ms=round(sum(walls) / len(walls) * 1e3, 3),
        tokens_per_s=round(batch * seq / min(walls), 1),
        costdb_collective_rows=sum(len(v) for v in
                                   db["collectives"].values()),
        costdb_gemm_classes=len(db["gemms"]),
        costdb_path=costdb_path, timeline_path=timeline_path,
        trace_dir=logdir, config=cfg, backend=jax.default_backend(),
    )

    def mean_pct(key):
        return round(sum(r[key] for r in rows) / len(rows), 2)

    if rows and on_tpu:
        fields.update(compute_pct=mean_pct("compute_pct"),
                      collective_exposed_pct=mean_pct(
                          "collective_exposed_pct"),
                      bubble_pct=mean_pct("bubble_pct"),
                      host_gap_pct=mean_pct("host_gap_pct"))
        status = "OK"
    else:
        reason = ("step anatomy needs per-HLO device events; this "
                  f"{jax.default_backend()} trace is host-only"
                  if not rows else
                  "anatomy percentages are a TPU measurement; this is a "
                  f"{jax.default_backend()} smoke run")
        for k in ("compute_pct", "collective_exposed_pct", "bubble_pct",
                  "host_gap_pct"):
            fields[k] = ("skipped", reason)
        fields["reason"] = reason
        status = "SKIP"

    record = monitor.get_registry().emit_profile(status, **fields)
    errors = monitor.validate(record)
    if errors:
        raise ValueError(f"profile bench record failed validation: {errors}")
    print(json.dumps(record))


def pipeline_main():
    """``python bench.py --pipeline`` — the pipeline-schedule leg: the
    zero-bubble schedule (``GPTConfig(pp_schedule="zb")``) vs the
    autodiff 1f1b baseline on the flagship GPT blocks through
    ``GPTPipeline`` at pp >= 2 — one jitted fwd+bwd per schedule under
    ``shard_map``, tokens/s from min-of-passes with ``spread_pct`` as the
    noise bar (the training bench's accounting), plus bubble %:
    MEASURED by ``prof.trace_reader.step_anatomy`` on a real TPU trace,
    and from the trace-time unit-cost geometry
    (``monitor.pipeline_cost_model``) everywhere. Both jitted paths are
    witnessed recompile-free across schedule-geometry reuse
    (``jit_cache_ok``: fresh data through the same geometry keeps the
    jit cache at 1).

    Emits ONE ``pipeline`` record through the monitor schema and prints
    it as one JSON line. ``status: "OK"`` requires a real multichip TPU;
    off-TPU the leg still runs end to end at smoke scale on a virtual
    8-device CPU mesh and the record is an explicit ``SKIP(reason)`` with
    the smoke numbers and geometry riding along. Never nan in an OK
    line."""
    # must precede the first backend query: the CPU platform only grows
    # virtual devices if the flag is set pre-initialization
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")
    on_tpu = jax.default_backend() == "tpu"
    monitor.enable_from_env()
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.parallel import mesh as mesh_lib
    from apex_tpu.transformer.pipeline_parallel import GPTPipeline

    n = jax.device_count()
    pp = 4 if (n % 4 == 0 and n >= 4) else (2 if n % 2 == 0 else 0)

    def emit(status, **fields):
        if monitor.enabled():
            record = monitor.get_registry().emit_pipeline(status, **fields)
        else:  # sink-less registry: same construction+honesty path
            record = monitor.MetricsRegistry().emit_pipeline(
                status, **fields)
        errors = monitor.validate(record)
        if errors:
            raise ValueError(
                f"pipeline bench record failed validation: {errors}")
        print(json.dumps(record))

    if pp < 2:
        emit("SKIP", reason=(f"a pipeline needs >= 2 stages; this "
                             f"{jax.default_backend()} host exposes {n} "
                             "device(s)"),
             backend=jax.default_backend())
        return

    if on_tpu:
        kw = dict(vocab_size=32768, max_seq_len=1024, hidden_size=1024,
                  num_layers=12, num_heads=8, attention_impl="flash",
                  remat=True, scan_layers=False)
        M, b, s, iters, passes = 2 * pp, 4, 1024, 10, 3
        cast = jnp.bfloat16
    else:  # smoke scale on the virtual mesh; the record is SKIP anyway
        kw = dict(vocab_size=128, max_seq_len=64, hidden_size=64,
                  num_layers=pp * 2, num_heads=4, attention_impl="flash")
        M, b, s, iters, passes = 2 * pp, 2, 32, 2, 2
        cast = None

    # the ParallelPlan spelling (ISSUE 12); the measured schedule is
    # still selected per leg below (zb vs the 1f1b baseline)
    from apex_tpu.plan import ParallelPlan
    model = GPTModel(GPTConfig(**kw, plan=ParallelPlan(
        pp=pp, pp_schedule="zb")))
    params = model.init(jr.PRNGKey(0))
    if cast is not None:
        params = jax.tree.map(
            lambda x: x.astype(cast)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
    pipe = GPTPipeline(model, pp=pp)
    part = pipe.partition(params)
    specs = pipe.param_specs(part)
    mesh = mesh_lib.make_mesh(pipeline_model_parallel_size=pp,
                              devices=jax.devices()[:pp])
    toks = jr.randint(jr.PRNGKey(1), (M, b, s), 0, kw["vocab_size"])
    tgts = jr.randint(jr.PRNGKey(2), (M, b, s), 0, kw["vocab_size"])

    def build_step(schedule):
        def run(p, t, g):
            lp = dict(p, stages=jax.tree.map(lambda x: x[0], p["stages"]))
            loss, grads = pipe.loss_and_grads(lp, t, g, schedule=schedule)
            grads["stages"] = jax.tree.map(lambda x: x[None],
                                           grads["stages"])
            return loss, grads

        return jax.jit(mesh_lib.shard_map(
            run, mesh=mesh, in_specs=(specs, P(), P()),
            out_specs=(P(), specs)))

    def measure(schedule):
        step = build_step(schedule)
        loss, _ = step(part, toks, tgts)  # compile+warm
        float(loss)
        # geometry-reuse witness: fresh data, same schedule geometry —
        # the jit cache must stay at 1 (no retrace per step)
        loss, _ = step(part, toks + 1, tgts)
        float(loss)
        cache_ok = step._cache_size() == 1
        times = []
        for _ in range(passes):
            t0 = time.perf_counter()
            for _ in range(iters):
                loss, _ = step(part, toks, tgts)
            float(loss)  # host fetch syncs the dependent chain
            times.append((time.perf_counter() - t0) / iters)
        return M * b * s / min(times), times, cache_ok, step

    def measured_bubble(step):
        """Mean step_anatomy bubble % from a real device trace (TPU); a
        ('skipped', reason) marker anywhere that is unavailable.
        step_anatomy pairs device windows with HOST step spans, so each
        traced execution is stamped with one (blocking inside the span —
        the wall time is honest, same contract as profile_main's)."""
        if not on_tpu:
            return ("skipped", "step_anatomy needs a TPU device trace; "
                               "off-TPU the chrome trace is host-only")
        import tempfile

        from apex_tpu.prof import trace_reader
        try:
            spans = []
            with tempfile.TemporaryDirectory() as logdir:
                jax.profiler.start_trace(logdir)
                for i in range(3):
                    t0 = time.monotonic_ns()
                    loss, _ = step(part, toks, tgts)
                    float(loss)  # block INSIDE the span window
                    spans.append({"kind": "span", "name": "step",
                                  "step": i, "t0_ns": t0,
                                  "dur_ns": time.monotonic_ns() - t0})
                jax.profiler.stop_trace()
                events = trace_reader.read_trace(logdir)
                rows = trace_reader.step_anatomy(spans, events)
            vals = [r["bubble_pct"] for r in rows
                    if isinstance(r.get("bubble_pct"), (int, float))]
            if not vals:
                return ("skipped", "trace carried no per-step device rows")
            return round(sum(vals) / len(vals), 2)
        except Exception as e:  # noqa: BLE001 — a broken trace must not
            return ("skipped", f"trace capture failed: {e}")  # kill the leg

    tps_zb, pass_times, cache_zb, step_zb = measure("zb")
    tps_1f1b, pass_times_b, cache_1f1b, step_1f1b = measure("1f1b")
    spread = (max(pass_times) - min(pass_times)) / min(pass_times)
    spread_b = (max(pass_times_b) - min(pass_times_b)) / min(pass_times_b)
    geo_zb = monitor.pipeline_cost_model(M, pp, 1, schedule="zb")
    geo_1f1b = monitor.pipeline_cost_model(M, pp, 1, schedule="1f1b")
    # the schedule's own traffic accounting: fwd ticks x one microbatch
    # activation (both directions add the dX sweep's mirror of it)
    act_bytes = b * s * kw["hidden_size"] * (2 if cast else 4)
    fields = dict(
        schedule="zb", pipeline_size=pp, virtual_chunks=1,
        num_microbatches=M, overlap_p2p=False,
        tokens_per_s=round(tps_zb, 1),
        tokens_per_s_1f1b=round(tps_1f1b, 1),
        vs_1f1b=round(tps_zb / tps_1f1b, 4),
        bubble_pct=measured_bubble(step_zb),
        bubble_pct_1f1b=measured_bubble(step_1f1b),
        bubble_pct_geometry=round(100 * geo_zb["bubble_fraction"], 2),
        bubble_pct_1f1b_geometry=round(
            100 * geo_1f1b["bubble_fraction"], 2),
        p2p_bytes_per_step=act_bytes * geo_zb["fwd_ticks"] * 2,
        jit_cache_ok=bool(cache_zb and cache_1f1b),
        spread_pct=round(spread * 100, 2),
        spread_pct_1f1b=round(spread_b * 100, 2),
        pass_times_ms=[round(t * 1e3, 2) for t in pass_times],
        pass_times_1f1b_ms=[round(t * 1e3, 2) for t in pass_times_b],
        config=kw, backend=jax.default_backend(),
    )
    if on_tpu:
        status = "OK"
    else:
        fields["reason"] = (
            "pipeline-schedule speedup is an ICI/bubble measurement; "
            f"this is a {jax.default_backend()} smoke run on a virtual "
            f"{n}-device mesh (pp={pp})")
        status = "SKIP"
    emit(status, **fields)


def plan_main(argv=None):
    """``python bench.py --plan [--costdb F] [--chips N]`` — the
    auto-parallelism planner leg (ISSUE 12): search → pick → measure.

    **Search**: enumerate the feasible plan lattice for ``--chips``
    (default: every visible device) over the flagship workload, price
    every candidate from the CostDB (``--costdb`` names a measured
    artifact from ``bench.py --profile --costdb``; without one, a
    uniform reference rate is used and every key is flagged
    uncalibrated), and rank by predicted step time
    (:func:`apex_tpu.plan.search.search_plans`).

    **Pick**: the chosen plan is JXP-gated in-process — the
    ``planned_gpt_step`` entrypoint traces it and checks donation +
    the schedule/overlap contracts its knobs engage (``lint_ok``); the
    planner can never ship a plan that violates a shipped invariant.

    **Measure**: the chosen plan's per-chip step program (the exact
    program the pricing traced, instantiated with real operands) is
    timed min-of-passes, and ``predicted_vs_measured_err_pct`` is
    recorded — the series ``tools/bench_history.py`` gates for drift.
    The schedule's warmup/drain enters *predicted* through the
    ``pipeline_cost_model`` factor while the measured per-chip program
    carries only the useful work, so the error series includes the
    schedule-model term by construction; DRIFT is what the gate
    watches. Memory is measured too (apexmem): the chosen plan's
    donation-aware liveness bound (``predicted_peak_hbm_mb``) is
    compared against ``memory_stats()['peak_bytes_in_use']`` into
    ``predicted_vs_measured_hbm_err_pct``, a second gated series. On
    TPU the record is ``status: "OK"``; off-TPU the measured halves
    ride as explicit skip objects (never nan in an OK line) with
    ``smoke_step_ms`` as the finite plumbing witness that the full
    search→pick→measure loop ran.
    """
    import sys

    # must precede the first backend query: the CPU platform only grows
    # virtual devices if the flag is set pre-initialization
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")
    on_tpu = jax.default_backend() == "tpu"
    monitor.enable_from_env()

    from apex_tpu.lint import entrypoints as lint_eps
    from apex_tpu.plan import Workload, plan_record_fields, search_plans
    from apex_tpu.prof.calibrate import validate_costdb

    argv = list(sys.argv[1:] if argv is None else argv)

    def _opt(flag, default):
        return argv[argv.index(flag) + 1] if flag in argv else default

    chips = int(_opt("--chips", jax.device_count()))
    costdb_path = _opt("--costdb", None)

    if on_tpu:
        # the flagship train-bench dims (bench `main()`'s config) at a
        # searchable batch geometry
        w = Workload(hidden_size=1024, num_layers=12, vocab_size=32768,
                     seq=1024, global_batch=16, micro_batch=2,
                     dtype_bytes=2, remat=False)
        iters, passes = 10, 3
    else:  # smoke scale; the record is SKIP either way
        w = Workload(hidden_size=64, ffn_hidden_size=256, num_layers=4,
                     vocab_size=256, seq=64, global_batch=8,
                     micro_batch=1, dtype_bytes=4, remat=False)
        iters, passes = 2, 2

    if costdb_path:
        with open(costdb_path) as fh:
            db = json.load(fh)
        errors = validate_costdb(db)
        if errors:
            raise ValueError(f"{costdb_path} is not a valid costdb: "
                             f"{errors}")
        source = costdb_path
    else:
        # no measured CostDB: the empty table makes every key a flagged
        # blind spot priced at the uniform reference floors — the
        # ranking reflects geometry alone, labeled, never silent
        db = {"schema": 1, "kind": "costdb", "collectives": {},
              "gemms": {}}
        source = "uniform-reference"
    # blind spots price at the SLOWEST measured rate (never 0 ms): a
    # plan must not win because its dominant traffic was never measured;
    # the memory column comes from the donation-aware LIVENESS walk of
    # each candidate's traced step (apexmem), with >10% closed-form
    # disagreement surfaced as a memory_model[...] honesty flag
    from apex_tpu.plan import conservative_defaults
    result = search_plans(chips, w, db, memory_source="liveness",
                          **conservative_defaults(db))
    best = result.best

    # JXP-gate the chosen plan through the registered entrypoint — the
    # same contracts `python -m apex_tpu.lint --jaxpr` enforces
    os.environ["APEX_TPU_PLAN"] = json.dumps(best.plan.to_json())
    try:
        findings, _cost = lint_eps.check("planned_gpt_step")
        lint_ok = not findings
    finally:
        os.environ.pop("APEX_TPU_PLAN", None)

    # measure the priced per-chip program (real operands, min-of-passes)
    from apex_tpu.plan import build_plan_step
    fn, sds_args = build_plan_step(best.plan, w)
    step = jax.jit(fn, donate_argnums=(0,))
    args = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), sds_args)
    params, x, tgt = args
    params, loss = step(params, x, tgt)  # compile+warm
    float(loss)
    times = []
    for _ in range(passes):
        t0 = time.perf_counter()
        for _ in range(iters):
            params, loss = step(params, x, tgt)
        float(loss)  # host fetch syncs the dependent chain
        times.append((time.perf_counter() - t0) / iters)
    measured_ms = min(times) * 1e3

    # apexmem: predicted peak HBM (the liveness bound of the measured
    # program, per chip) vs the device allocator's high-water. The
    # measured side exists only on TPU with memory_stats(); off-TPU it
    # rides as explicit skip objects — never nan in an OK line.
    from apex_tpu.plan import liveness_memory
    predicted_peak_mb = round(liveness_memory(best.plan, w).total
                              / 2 ** 20, 2)
    measured_peak_mb = None
    if on_tpu:
        stats = jax.local_devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        if peak is not None:
            measured_peak_mb = round(peak / 2 ** 20, 2)

    skip_reason = (None if on_tpu else
                   f"plan step-time is a TPU measurement; this is a "
                   f"{jax.default_backend()} smoke run on a virtual "
                   f"{jax.device_count()}-device mesh")
    fields = plan_record_fields(
        result, costdb_source=source,
        measured_step_ms=measured_ms if on_tpu else None,
        skip_reason=skip_reason)
    no_stats = "device memory_stats unavailable on this backend"
    skip = lambda r: ("skipped", r)  # noqa: E731
    if measured_peak_mb is not None:
        hbm_err = (100.0 * abs(predicted_peak_mb - measured_peak_mb)
                   / measured_peak_mb)
        fields["measured_peak_hbm_mb"] = measured_peak_mb
        fields["predicted_vs_measured_hbm_err_pct"] = round(hbm_err, 3)
    else:
        reason = skip_reason or no_stats
        fields["measured_peak_hbm_mb"] = skip(reason)
        fields["predicted_vs_measured_hbm_err_pct"] = skip(reason)
    fields.update(
        predicted_peak_hbm_mb=predicted_peak_mb,
        lint_ok=bool(lint_ok),
        smoke_step_ms=round(measured_ms, 4),
        config={"hidden_size": w.hidden_size, "num_layers": w.num_layers,
                "vocab_size": w.vocab_size, "seq": w.seq,
                "global_batch": w.global_batch,
                "micro_batch": w.micro_batch, "remat": w.remat},
        backend=jax.default_backend(),
    )
    if on_tpu:
        status = "OK"
    else:
        fields["reason"] = skip_reason
        status = "SKIP"

    if monitor.enabled():
        record = monitor.get_registry().emit_plan(status, **fields)
    else:  # sink-less registry: same construction+honesty path, no file
        record = monitor.MetricsRegistry().emit_plan(status, **fields)
    errors = monitor.validate(record)
    if errors:
        raise ValueError(f"plan bench record failed validation: {errors}")
    print(json.dumps(record))


def ckpt_main():
    """``python bench.py --ckpt`` — the elastic-checkpoint leg: a GPT
    train loop with dp-sharded ZeRO Adam, checkpointed through
    ``apex_tpu.ckpt.ZeroCheckpointManager`` async saves. Measures the
    steady clean step (min-of-passes), the mean step while a save is in
    flight (``save_overhead_pct`` = the extra wall per step a saving
    run pays — the lower-is-better series ``tools/bench_history.py``
    gates), the snapshot (on-path) vs write (background) split, restore
    time, and runs BOTH acceptance witnesses in-process: same-dp
    restore bitwise (masters/m/v identical) and elastic dp-resize row
    parity. One ``ckpt`` record; ``status: "OK"`` requires a real TPU,
    off-TPU the leg runs at smoke scale on the virtual 8-device CPU
    mesh and the record is an explicit ``SKIP(reason)`` with the smoke
    numbers riding along. Never nan in an OK line."""
    import shutil
    import tempfile

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")
    on_tpu = jax.default_backend() == "tpu"
    monitor.enable_from_env()
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    from apex_tpu import ckpt as ckpt_lib
    from apex_tpu.contrib.optimizers import distributed_fused_adam
    from apex_tpu.contrib.optimizers.distributed import gather_zero_state
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.parallel import mesh as mesh_lib

    def emit(status, **fields):
        if monitor.enabled():
            record = monitor.get_registry().emit_ckpt(status, **fields)
        else:
            record = monitor.MetricsRegistry().emit_ckpt(status, **fields)
        errors = monitor.validate(record)
        if errors:
            raise ValueError(
                f"ckpt bench record failed validation: {errors}")
        print(json.dumps(record))

    dp = jax.device_count()
    if dp < 2:
        emit("SKIP", reason=(f"elastic ZeRO checkpointing needs dp >= 2; "
                             f"this {jax.default_backend()} host exposes "
                             f"{dp} device(s)"),
             backend=jax.default_backend())
        return

    if on_tpu:
        kw = dict(vocab_size=32768, max_seq_len=1024, hidden_size=1024,
                  num_layers=12, num_heads=8, attention_impl="flash",
                  remat=False, scan_layers=False)
        b, s, iters, passes, save_every = 2 * dp, 1024, 10, 3, 4
    else:  # smoke scale; the record is SKIP anyway
        kw = dict(vocab_size=256, max_seq_len=64, hidden_size=64,
                  num_layers=2, num_heads=4, attention_impl="flash")
        b, s, iters, passes, save_every = dp, 32, 4, 2, 2

    mesh = mesh_lib.make_mesh()
    model = GPTModel(GPTConfig(**kw))
    params = model.init(jr.PRNGKey(0))
    zopt = distributed_fused_adam(learning_rate=1e-3)
    toks = jr.randint(jr.PRNGKey(1), (b, s), 0, kw["vocab_size"])
    tgts = jr.randint(jr.PRNGKey(2), (b, s), 0, kw["vocab_size"])

    def zero_step(p, t, g, st):
        loss, grads = jax.value_and_grad(model.loss_fn)(p, t, g)
        updates, st = zopt.update(grads, st, p)
        return optax.apply_updates(p, updates), st, jax.lax.pmean(
            loss, "dp")

    step = jax.jit(mesh_lib.shard_map(
        zero_step, mesh=mesh, in_specs=(P(), P("dp"), P("dp"), P()),
        out_specs=(P(), P(), P())))
    zstate = mesh_lib.shard_map(lambda p: zopt.init(p), mesh=mesh,
                                in_specs=P(), out_specs=P())(params)
    params, zstate, loss = step(params, toks, tgts, zstate)  # compile
    float(loss)

    # clean steady-state step: min-of-passes (the training bench's rule)
    times = []
    for _ in range(passes):
        t0 = time.perf_counter()
        for _ in range(iters):
            params, zstate, loss = step(params, toks, tgts, zstate)
        float(loss)
        times.append((time.perf_counter() - t0) / iters)
    step_ms = min(times) * 1e3
    spread = (max(times) - min(times)) / min(times)

    root = tempfile.mkdtemp(prefix="apex_tpu_ckpt_bench_")
    try:
        snapshot_ms = write_ms = None
        with ckpt_lib.ZeroCheckpointManager(root, max_to_keep=2) as mgr:
            # the saving pass: same step loop, one async save every
            # save_every steps — the snapshot is the only on-path part
            nsteps = iters * passes
            saves = 0
            t0 = time.perf_counter()
            for i in range(nsteps):
                params, zstate, loss = step(params, toks, tgts, zstate)
                if i % save_every == 0:
                    float(loss)  # the step really finished; snapshot
                    # BETWEEN steps, exactly the train-loop contract
                    g = gather_zero_state(zstate, mesh)
                    mgr.save(i, g, dp=dp, params=params, force=True)
                    saves += 1
            float(loss)
            # the clock stops BEFORE draining the final background
            # write: save_overhead_pct claims per-STEP overhead (the
            # snapshot is the only on-path part), and the last write's
            # drain is off-step disk time — folding it in would make
            # the lower-is-better gate track disk speed, not the saver
            step_saving_ms = (time.perf_counter() - t0) / nsteps * 1e3
            mgr.wait_until_finished()
            snapshot_ms = mgr.last_timings.get("snapshot_ms")
            write_ms = mgr.last_timings.get("write_ms")

            # the acceptance witnesses, measured on the live state
            g_final = gather_zero_state(zstate, mesh)
            final_dir = os.path.join(root, "final")
            manifest = ckpt_lib.save_zero_sharded(
                final_dir, g_final, dp=dp, params=params, step=nsteps)
            t0 = time.perf_counter()
            st_same, _ = ckpt_lib.load_zero_state(final_dir, params,
                                                  dp=dp)
            restore_ms = (time.perf_counter() - t0) * 1e3
            bitwise = all(
                np.array_equal(np.asarray(g_final.buffers[k]),
                               np.asarray(st_same.buffers[k]))
                for k in st_same.buffers)
            dp2 = dp // 2
            st_el, _ = ckpt_lib.load_zero_state(final_dir, params,
                                                dp=dp2)
            n_rows = manifest.n_chunks
            elastic = all(
                np.array_equal(np.asarray(g_final.buffers[k])[:n_rows],
                               np.asarray(st_el.buffers[k])[:n_rows])
                for k in st_el.buffers)
            bytes_written = sum(
                os.path.getsize(os.path.join(final_dir, f))
                for f in os.listdir(final_dir))
    finally:
        shutil.rmtree(root, ignore_errors=True)

    fields = dict(
        save_overhead_pct=round(
            max(100.0 * (step_saving_ms - step_ms) / step_ms, 0.0), 2),
        step_ms=round(step_ms, 3),
        step_ms_saving=round(step_saving_ms, 3),
        snapshot_ms=(round(snapshot_ms, 3)
                     if isinstance(snapshot_ms, (int, float))
                     else ("skipped", "no async save landed")),
        write_ms=(round(write_ms, 3)
                  if isinstance(write_ms, (int, float))
                  else ("skipped", "no async save landed")),
        restore_ms=round(restore_ms, 3),
        bytes_written=int(bytes_written),
        steps=nsteps, saves=saves, save_every=save_every, dp=dp,
        async_save=True,
        bitwise_resume_ok=bool(bitwise),
        elastic_resume_ok=bool(elastic),
        manifest=manifest.summary(),
        spread_pct=round(spread * 100, 2),
        config=kw, backend=jax.default_backend(),
    )
    if not (bitwise and elastic):
        raise AssertionError(
            f"checkpoint resume witnesses failed: bitwise={bitwise} "
            f"elastic={elastic} — the ckpt record must not ship")
    if on_tpu:
        status = "OK"
    else:
        fields["reason"] = (
            "checkpoint save overhead is a device-transfer + disk "
            f"measurement; this is a {jax.default_backend()} smoke run "
            f"on a virtual {dp}-device mesh")
        status = "SKIP"
    if mgr.last_trace_id:  # join the record to its last save's
        fields["trace_id"] = mgr.last_trace_id  # ckpt_save_start/commit
    emit(status, **fields)
    mesh_lib.destroy_model_parallel()


def main():
    on_tpu = jax.default_backend() == "tpu"
    monitor.enable_from_env()  # APEX_TPU_MONITOR=<path> streams JSONL
    if on_tpu:
        # remat=False: the un-rematted step fits 16G since the
        # vocab-parallel CE stopped saving an fp32 softmax residual
        # (recompute-from-lse backward) — measured 75.3k vs 71.3k tok/s
        # against the previous mlp_only policy.
        # scan_layers=False: at 12 layers the unrolled program removes the
        # scan carry's copy/DUS overhead (measured +7%: 70.8k vs 66.0k
        # tok/s) for ~10s extra compile.
        # num_heads=8 (head_dim 128, not 16x64): the MXU contracts/emits
        # 128 lanes, so d=64 runs the attention kernels at half lane
        # utilization — measured 76.4k vs 98.2k tok/s (+28%) at identical
        # hidden/layers/params/FLOPs. Same hardware reasoning as
        # Llama-class models' head_dim=128.
        cfg = dict(vocab_size=32768, max_seq_len=1024, hidden_size=1024,
                   num_layers=12, num_heads=8, tp_size=1, remat=False,
                   attention_impl="flash", scan_layers=False)
        # batch 20, re-probed after the in-kernel-delta backward landed:
        # same-process sweep measured b16 111.5k / b20 116.4k / b24 116.1k
        # tok/s (b16 won every sweep before it; b32 OOM-thrashes at 94k) —
        # the shorter prologue moved the knee up one notch.
        batch, seq, iters = 20, 1024, 20
    else:  # smoke-test scale for CPU runs
        cfg = dict(vocab_size=1024, max_seq_len=128, hidden_size=128,
                   num_layers=2, num_heads=4, tp_size=1, remat=False,
                   attention_impl="flash")
        batch, seq, iters = 2, 128, 3

    tokens = jr.randint(jr.PRNGKey(1), (batch, seq), 0, cfg["vocab_size"])
    targets = jr.randint(jr.PRNGKey(2), (batch, seq), 0, cfg["vocab_size"])

    # Donation is PINNED on, applied to BOTH impls (VERDICT r3 weak #7):
    # the probe that used to pick it could only coin-flip — r4 measured
    # the two settings at parity across repeated runs (115.6–116.7k tok/s
    # both ways) and shorter probe loops are noisier than any honest
    # decision margin. Donating is the memory-safer choice (params+opt
    # state update in place). Noise accounting (VERDICT r4 weak #3): the
    # HEADLINE is min-of-3 passes; spread_pct = (max-min)/min across the
    # passes is the per-run noise bar and the raw pass times ship in the
    # artifact.
    donate = True

    if monitor.enabled():
        monitor.emit_meta(
            device_kind=jax.devices()[0].device_kind if on_tpu else "cpu",
            backend=jax.default_backend(),
            model_flops_per_token=model_flops_per_token(cfg, seq),
            batch=batch, seq=seq, iters=iters, config=cfg,
            metric="gpt_medium_train_step_throughput",
        )

    results = {}
    pass_times = []
    for impl in ("baseline", "fused"):
        os.environ["APEX_TPU_PALLAS"] = "0" if impl == "baseline" else "1"
        step, params, opt_state = build(impl, cfg, donate)
        if impl == "fused":
            # only the fused (framework) passes are the headline; their
            # step records are what `monitor report` reproduces tokens/s from
            results[impl], pass_times = timeit(
                step, params, opt_state, tokens, targets, iters,
                return_passes=True, monitor_tokens=batch * seq)
        else:
            results[impl] = timeit(
                step, params, opt_state, tokens, targets, iters)
        del step, params, opt_state
    spread = (max(pass_times) - min(pass_times)) / min(pass_times)

    tokens_per_s = batch * seq / results["fused"]
    vs_baseline = results["baseline"] / results["fused"]
    flops_per_s = model_flops_per_token(cfg, seq) * tokens_per_s
    peak = (monitor.spec_peak_flops(jax.devices()[0].device_kind)
            if on_tpu else None)
    result = {
        "metric": "gpt_medium_train_step_throughput",
        "value": round(tokens_per_s, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(vs_baseline, 4),
        "mfu": round(flops_per_s / peak, 4) if peak else None,
        "model_tflops": round(flops_per_s / 1e12, 2),
        "donated": donate,
        "spread_pct": round(spread * 100, 2),
        "pass_times_ms": [round(t * 1e3, 2) for t in pass_times],
    }
    # the artifact is schema-checked before it is printed: a nan/inf in a
    # bench result must crash the bench, never ship inside a success line
    errors = monitor.validate(result)
    if errors:
        raise ValueError(f"bench artifact failed validation: {errors}")
    if monitor.enabled():
        monitor.emit_event("bench_result", **result)
    print(json.dumps(result))


if __name__ == "__main__":
    import sys

    from apex_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "--profile" in sys.argv[1:]:
        profile_main([a for a in sys.argv[1:] if a != "--profile"])
    elif "--decode" in sys.argv[1:]:
        decode_main()
    elif "--serve" in sys.argv[1:]:
        if "--plan-serve" in sys.argv[1:]:
            plan_serve_main(sys.argv[1:])
        elif "--plan-tp" in sys.argv[1:]:
            tp_serve_main(sys.argv[1:])
        else:
            serve_main()
    elif "--longseq-bias" in sys.argv[1:]:
        longseq_bias_main()
    elif "--tp-overlap" in sys.argv[1:]:
        tp_overlap_main()
    elif "--pipeline" in sys.argv[1:]:
        pipeline_main()
    elif "--plan" in sys.argv[1:]:
        plan_main([a for a in sys.argv[1:] if a != "--plan"])
    elif "--ckpt" in sys.argv[1:]:
        ckpt_main()
    elif "--spec" in sys.argv[1:]:
        spec_main(tree="--tree" in sys.argv[1:])
    else:
        main()
