"""Device time of the attention blocks — everything traced under ``gpt/attn``,
``hybrid/attn``, ``hybrid/attn_win`` or ``hybrid/attn_mla``: the block's norm, the
projections and their gradients, what places q and k, the flash kernels, the
output projection, forward, recomputed and backward — per traced step, mean
over chips."""
from benchmarks import scope_work

LAYER = "blocks"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ("gpt/attn", "hybrid/attn", "hybrid/attn_win", "hybrid/attn_mla")


def read(run):
    return scope_work.scope_ms(run, SPANS)
