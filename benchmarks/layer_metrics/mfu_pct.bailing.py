"""``mfu_pct.hybrid``'s reading for the ``bailing_hybrid`` cell: required
operations per token as the adapter hands them in
``run["train_flops_per_token"]`` (``bailing_work.window_flops_per_token``: every
matmul weight a token meets with the routed experts at the counted local
assignments, the per-channel delta rule in its chunked form at the causal
half and the convolution in five layers, latent attention at 640 operations a
score pair and head over the causal half in one, the head over the vocabulary
slice; times 3, nothing recomputed), times tokens per second, over chips times
the chip's bf16 peak.
A twin only because an accepted metric's cell list may not be appended to (PERF.md section 7)."""

LAYER = "trainer step"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    need = run.get("train_flops_per_token")
    if need is None or "tokens" not in run:
        return None
    rate = run["tokens"] / run["window_s"]
    return 100.0 * need * rate / (run["chips"] * run["peaks"]["flops_per_s"]["bfloat16"])
