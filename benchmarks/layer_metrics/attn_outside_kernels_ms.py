"""Device time of the attention blocks (``attn_block_ms``) outside their flash
kernels: the same spans less the operations whose name holds ``flash_``, per
traced step, mean over chips. What a faster kernel cannot touch."""
from benchmarks import scope_work

LAYER = "blocks"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ("gpt/attn", "hybrid/attn", "hybrid/attn_win", "hybrid/attn_mla")
KERNELS = ("flash_",)


def read(run):
    return scope_work.scope_ms(run, SPANS, minus=KERNELS)
