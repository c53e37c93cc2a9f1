"""The banded backward flash kernels' share of their roofline: the least
time for the band's backward work (twice the forward's operations; q, k, v,
o, do, dq, dk, dv once) over ``flash_win_bwd_ms``."""
from benchmarks import afmoe_work

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    return afmoe_work.band_roofline_pct(run, backward=True)
