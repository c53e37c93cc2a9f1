"""Device time of the delta-rule mixer blocks (``gdn_block_ms``) outside their
kernels: ``hybrid/gdn`` less the operations whose name holds ``gdn_``,
``conv_silu`` or ``gated_norm``, per traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "blocks"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ("hybrid/gdn",)
KERNELS = ("gdn_", "conv_silu", "gated_norm")


def read(run):
    return scope_work.scope_ms(run, SPANS, minus=KERNELS)
