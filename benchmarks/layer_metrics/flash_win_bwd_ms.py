"""Device time of the banded (sliding-window) backward flash kernels (Mosaic
calls whose name holds ``flash_bwd_bshd_win``: dq and dkv), per traced step,
mean over chips. They count into ``flash_bwd_ms`` too: this is their part."""
from benchmarks import afmoe_work, kernel_work

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    return kernel_work.kernel_ms(run, afmoe_work.BAND_BACKWARD)
