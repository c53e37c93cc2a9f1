"""Device time of the expert blocks: everything traced under ``hybrid/moe``
(routing, the row movements, the grouped products, the shared experts, the
block's norm), per traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "experts (dropless routing)"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ("hybrid/moe",)


def read(run):
    return scope_work.scope_ms(run, SPANS)
