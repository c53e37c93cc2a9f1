"""Device time of the backward flash-attention kernels (Mosaic calls whose
name holds ``flash_bwd``: dq, dkv and the fused single-block kernel), per
traced step, mean over chips."""
from benchmarks import kernel_work

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    return kernel_work.kernel_ms(run, kernel_work.FLASH_BACKWARD)
