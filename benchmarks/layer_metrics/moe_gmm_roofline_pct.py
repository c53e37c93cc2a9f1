"""The grouped expert products' share of their roofline: the least time for
the required (operations, bytes) of one step, as the cell's adapter hands
them in ``run["expert_matmul_work"]``, over ``moe_gmm_ms``. The adapter knows
its model and the reader none: ``hybrid_work.window_expert_matmul_work``
counts the window's local assignments' operations (a gated expert 6 H F
forward, an ungated one 4 H F, twice that backward, never at a padded width)
and bytes (the held experts' weights of the expert layers once a pass —
forward, dx, dw — and the gathered rows in and out). A forward pass recomputed
in the backward pass counts in the time and not in the work. A run that hands
no work reads as nothing."""
from benchmarks import hybrid_work

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    work = run.get("expert_matmul_work")
    if work is None:
        return None
    return hybrid_work.roofline_pct(run, hybrid_work.EXPERT_MATMUL, work)
