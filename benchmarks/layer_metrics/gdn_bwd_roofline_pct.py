"""The backward delta-rule kernels' share of their roofline: twice the
forward's operations, and q, k, v, o, do, dq, dk, dv, g, beta, dg, dbeta
once (``hybrid_work.delta_rule_work``), over ``gdn_bwd_ms``."""
from benchmarks import hybrid_work

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    if "layer_types" not in run.get("dims", {}):
        return None
    return hybrid_work.roofline_pct(run, hybrid_work.GDN_BACKWARD, hybrid_work.delta_rule_work(
        run["dims"], hybrid_work.step_tokens(run), backward=True))
