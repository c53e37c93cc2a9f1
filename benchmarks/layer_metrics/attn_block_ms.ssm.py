"""``attn_block_ms`` for the ``nemotron_h`` cell: device time of its one
attention block (everything traced under ``hybrid/attn``: the norm, the
projections, the flash kernels, ``w_o``), per traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "blocks"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ("hybrid/attn",)


def read(run):
    return scope_work.scope_ms(run, SPANS)
