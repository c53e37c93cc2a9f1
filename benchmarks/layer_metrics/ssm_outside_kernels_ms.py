"""Device time of the state-space mixer blocks (``ssm_block_ms``) outside their
kernels: ``hybrid/ssm`` less the operations whose name holds ``ssd_``,
``conv_silu`` or ``gated_norm``, per traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "blocks"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ("hybrid/ssm",)
KERNELS = ("ssd_", "conv_silu", "gated_norm")


def read(run):
    return scope_work.scope_ms(run, SPANS, minus=KERNELS)
