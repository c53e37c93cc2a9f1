"""``mfu_pct.hybrid``'s reading for the ``ouro`` cell: required operations per
token as the adapter hands them in ``run["train_flops_per_token"]``
(``loop_work.window_flops_per_token``: every matmul weight of the layers once
a WALK — four times a token —, causal attention over its half layers x walks
times, the head over the whole vocabulary once an exit, the gate; times 3,
nothing recomputed), times tokens per second, over chips times the chip's
bf16 peak. A twin only because an accepted metric's cell list may not be
appended to (PERF.md section 7)."""

LAYER = "trainer step"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    need = run.get("train_flops_per_token")
    if need is None or "tokens" not in run:
        return None
    rate = run["tokens"] / run["window_s"]
    return 100.0 * need * rate / (run["chips"] * run["peaks"]["flops_per_s"]["bfloat16"])
