"""``attn_outside_kernels_ms`` for the ``ouro`` cell: device time of its
attention halves (``attn_block_ms.loop``) outside their flash kernels: the
same span less the operations whose name holds ``flash_``, per traced step,
mean over chips. What a faster kernel cannot touch."""
from benchmarks import scope_work

LAYER = "blocks"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ("hybrid/attn",)
KERNELS = ("flash_",)


def read(run):
    return scope_work.scope_ms(run, SPANS, minus=KERNELS)
