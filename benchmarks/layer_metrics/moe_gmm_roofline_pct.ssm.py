"""``moe_gmm_roofline_pct`` for the ``nemotron_h`` cell: the least time for the
counted local assignments' operations (an ungated expert is two matrices: 4 H
F forward, twice that backward, at F = 1,856 and never a padded width) and
bytes (the held experts' weights of the expert layers once a pass — forward,
dx, dw — and the gathered rows in and out) over ``moe_gmm_ms.ssm``."""
from benchmarks import hybrid_work, ssm_work

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    assignments = hybrid_work.assignments_per_step(run)
    if assignments is None or "ssm" not in run.get("dims", {}).get("kinds", ()):
        return None
    return hybrid_work.roofline_pct(run, hybrid_work.EXPERT_MATMUL,
                                    ssm_work.expert_matmul_work(run["dims"], assignments))
