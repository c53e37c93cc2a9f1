"""Required operations per token, as the adapter hands them in
``run["train_flops_per_token"]`` (``hybrid_work.window_flops_per_token``: every
matmul weight a token meets with the routed experts at the counted local
assignments, causal attention at its half, the recurrence's own count, the
convolution, the head over the vocabulary slice; times 3, nothing
recomputed), times tokens per second, over chips times the chip's bf16 peak.
A reader that takes the count from the run needs to know no model: a later
``benchmark`` issue can have every adapter hand the key and ``mfu_pct`` read
it (PERF.md section 7)."""

LAYER = "trainer step"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    need = run.get("train_flops_per_token")
    if need is None or "tokens" not in run:
        return None
    rate = run["tokens"] / run["window_s"]
    return 100.0 * need * rate / (run["chips"] * run["peaks"]["flops_per_s"]["bfloat16"])
