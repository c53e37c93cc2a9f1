"""Device time of the backward kernels of the delta rule with a decay a key
channel (Mosaic calls whose name holds ``kda_bwd``: once a delta-rule layer and
step), per traced step, mean over chips. The convolution around it is
``conv_silu_*`` and is not in it."""
from benchmarks import bailing_work, kernel_work

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    return kernel_work.kernel_ms(run, bailing_work.KDA_BACKWARD)
