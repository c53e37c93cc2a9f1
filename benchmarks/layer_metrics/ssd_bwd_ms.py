"""Device time of the backward state-space scan kernels (Mosaic calls whose
name holds ``ssd_bwd``), per traced step, mean over chips."""
from benchmarks import kernel_work, ssm_work

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    return kernel_work.kernel_ms(run, ssm_work.SCAN_BACKWARD)
