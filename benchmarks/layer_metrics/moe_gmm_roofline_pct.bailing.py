"""``moe_gmm_roofline_pct`` for the ``bailing_hybrid`` cell: the least time for the
counted local assignments' operations (6 H F forward, twice that backward, at
F = 768) and bytes (the held experts' weights of the expert layers once a pass
— forward, dx, dw — and the gathered rows in and out) over
``moe_gmm_ms.bailing``."""
from benchmarks import bailing_work, hybrid_work, mla_work

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    assignments = hybrid_work.assignments_per_step(run)
    if assignments is None or not bailing_work.is_bailing(run):
        return None
    return hybrid_work.roofline_pct(run, hybrid_work.EXPERT_MATMUL, hybrid_work.expert_matmul_work(
        mla_work.expert_view(run["dims"]), assignments, passes=3))
