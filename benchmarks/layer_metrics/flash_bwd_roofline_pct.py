"""The backward flash kernels' share of their roofline: the least time the
chip could take for the attention the backward pass requires (twice the
forward's operations; ``kernel_work.flash_work``) over ``flash_bwd_ms``."""
from benchmarks import kernel_work

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    return kernel_work.flash_roofline_pct(run, backward=True)
