"""Device time of the banded (sliding-window) forward flash kernels (Mosaic
calls whose name holds ``flash_fwd_bshd_win``), per traced step, mean over
chips. They count into ``flash_fwd_ms`` too: this is their part of it."""
from benchmarks import afmoe_work, kernel_work

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    return kernel_work.kernel_ms(run, afmoe_work.BAND_FORWARD)
