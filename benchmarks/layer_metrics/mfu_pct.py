"""The whole step's share of the chip's peak: required operations per token,
as the cell's adapter hands them in ``run["train_flops_per_token"]``, times
tokens per second, over chips times the chip's bf16 peak. The adapter knows
its model and the reader none: ``flops.train_flops_per_token`` for the GPT
block (causal, multi-query aware), the block's ``*_work.window_flops_per_token``
for the others (every matmul weight a token meets with the routed experts at
the window's counted local assignments, the mixers' own counts, the head over
the vocabulary slice; a looped stack once a walk). Forward times 3, nothing
recomputed. A run that hands no count reads as nothing."""

LAYER = "trainer step"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    need = run.get("train_flops_per_token")
    if need is None or "tokens" not in run:
        return None
    rate = run["tokens"] / run["window_s"]
    return 100.0 * need * rate / (run["chips"] * run["peaks"]["flops_per_s"]["bfloat16"])
