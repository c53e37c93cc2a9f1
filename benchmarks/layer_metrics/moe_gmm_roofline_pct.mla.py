"""``moe_gmm_roofline_pct`` for the ``deepseek_v2`` cell: the least time for the
counted local assignments' operations (6 H F forward, twice that backward)
and bytes (the held experts' weights of the expert layers once a pass —
forward, dx, dw — and the gathered rows in and out) over ``moe_gmm_ms.mla``."""
from benchmarks import hybrid_work, mla_work

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    assignments = hybrid_work.assignments_per_step(run)
    if assignments is None or "kv_lora_rank" not in run.get("dims", {}):
        return None
    return hybrid_work.roofline_pct(run, hybrid_work.EXPERT_MATMUL, hybrid_work.expert_matmul_work(
        mla_work.expert_view(run["dims"]), assignments, passes=3))
