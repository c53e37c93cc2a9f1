"""Device time of the forward flash-attention kernels (Mosaic calls whose
name holds ``flash_fwd``), per traced step, mean over chips."""
from benchmarks import kernel_work

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    return kernel_work.kernel_ms(run, kernel_work.FLASH_FORWARD)
