"""``moe_block_ms`` for the ``nemotron_h`` cell: device time of the expert
blocks, everything traced under ``hybrid/moe`` (routing, the row movements
with their pad and slice, the grouped products, the shared expert of 3,712,
the block's norm), per traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "experts (dropless routing)"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ("hybrid/moe",)


def read(run):
    return scope_work.scope_ms(run, SPANS)
