"""Required operations per token (``flops.train_flops_per_token``: causal,
multi-query aware, nothing recomputed) times tokens per second, over chips
times the chip's bf16 peak."""
from benchmarks import flops

LAYER = "trainer step"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    if "tokens" not in run:
        return None
    rate = run["tokens"] / run["window_s"]
    need = flops.train_flops_per_token(run["dims"], run["seq"])
    return 100.0 * need * rate / (run["chips"] * run["peaks"]["flops_per_s"]["bfloat16"])
