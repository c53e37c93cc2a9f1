"""The grouped expert products' share of their roofline: the least time for
the counted local assignments' operations (6 H F forward, twice that
backward) and bytes (the held experts' weights once a pass — forward, dx, dw
— and the gathered rows in and out) over ``moe_gmm_ms``. A forward pass
recomputed in the backward pass counts in the time and not in the work."""
from benchmarks import hybrid_work

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    assignments = hybrid_work.assignments_per_step(run)
    if assignments is None:
        return None
    return hybrid_work.roofline_pct(run, hybrid_work.EXPERT_MATMUL, hybrid_work.expert_matmul_work(
        run["dims"], assignments, passes=3))
