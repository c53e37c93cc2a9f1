"""Device time of the backward gated delta-rule kernels (Mosaic calls whose
name holds ``gdn_bwd``), per traced step, mean over chips."""
from benchmarks import hybrid_work, kernel_work

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    return kernel_work.kernel_ms(run, hybrid_work.GDN_BACKWARD)
