"""Device time of the per-channel delta-rule mixer blocks (``kda_block_ms``)
outside their kernels: ``hybrid/kda`` less the operations whose name holds
``kda_`` or ``conv_silu``, per traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "blocks"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ('hybrid/kda',)
KERNELS = ('kda_', 'conv_silu')


def read(run):
    return scope_work.scope_ms(run, SPANS, minus=KERNELS)
