"""The forward flash kernels' share of their roofline: the least time the
chip could take for the attention the forward pass requires
(``kernel_work.flash_work``) over ``flash_fwd_ms``."""
from benchmarks import kernel_work

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    return kernel_work.flash_roofline_pct(run)
