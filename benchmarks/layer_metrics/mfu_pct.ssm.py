"""``mfu_pct.hybrid``'s reading for the ``nemotron_h`` cell: required
operations per token as the adapter hands them in
``run["train_flops_per_token"]`` (``ssm_work.window_flops_per_token``: every
matmul weight a token meets with the routed experts — two matrices each, at
1,856 — at the counted local assignments, the state-space scan in its chunked
form at the causal half, the convolution, the one attention layer over the
causal half, the head over the vocabulary slice; times 3, nothing
recomputed), times tokens per second, over chips times the chip's bf16 peak.
A twin only because an accepted metric's cell list may not be appended to
(PERF.md section 7)."""

LAYER = "trainer step"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    need = run.get("train_flops_per_token")
    if need is None or "tokens" not in run:
        return None
    rate = run["tokens"] / run["window_s"]
    return 100.0 * need * rate / (run["chips"] * run["peaks"]["flops_per_s"]["bfloat16"])
