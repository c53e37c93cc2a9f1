"""``attn_block_ms`` for the ``ouro`` cell: device time of its attention
halves, every layer once a walk (everything traced under ``hybrid/attn``: the
two norms, the projections, rotary, the flash kernels, ``w_o``), per traced
step, mean over chips."""
from benchmarks import scope_work

LAYER = "blocks"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ("hybrid/attn",)


def read(run):
    return scope_work.scope_ms(run, SPANS)
