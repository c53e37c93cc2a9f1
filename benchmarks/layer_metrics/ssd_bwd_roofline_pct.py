"""The backward scan kernels' share of their roofline: twice the forward's
operations, the four inputs and ``dy`` in and the four cotangents out once
(``ssm_work.scan_work(backward=True)``), over ``ssd_bwd_ms``. Rebuilding the
chunks' states is the kernel's choice, not required work."""
from benchmarks import ssm_work

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    return ssm_work.scan_roofline_pct(run, backward=True)
