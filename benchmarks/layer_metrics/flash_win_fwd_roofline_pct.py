"""The banded forward flash kernels' share of their roofline: the least time
the chip could take for the band the forward pass requires
(``afmoe_work.attention_work``: a query scores ``min(i + 1, window)`` keys;
q, k, v, o once) over ``flash_win_fwd_ms``. A forward pass recomputed in the
backward pass counts in the time and not in the work."""
from benchmarks import afmoe_work

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    return afmoe_work.band_roofline_pct(run)
