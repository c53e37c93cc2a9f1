"""``moe_block_ms`` for the ``bailing_hybrid`` cell: device time of the expert
blocks, everything traced under ``hybrid/moe`` (the group-limited routing, the
row movements, the grouped products, the shared expert, the block's norm), per
traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "experts (dropless routing)"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ('hybrid/moe',)


def read(run):
    return scope_work.scope_ms(run, SPANS)
