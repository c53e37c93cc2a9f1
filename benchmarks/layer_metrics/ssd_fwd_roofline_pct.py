"""The forward scan kernels' share of their roofline: the least time the chip
could take for the scan the forward pass requires (``ssm_work.scan_work``:
the chunked form at the published chunk with the causal half of the products
inside a chunk; ``x``, ``dt``, ``B``, ``C`` in and ``y`` out once) over
``ssd_fwd_ms``."""
from benchmarks import ssm_work

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    return ssm_work.scan_roofline_pct(run)
