"""The forward delta-rule kernels' share of their roofline: the least time
the chip could take for the recurrence the forward pass requires
(``hybrid_work.delta_rule_work``: 6 dk dv operations a token and value head;
q, k, v, o, g, beta once) over ``gdn_fwd_ms``. A forward pass recomputed in
the backward pass counts in the time and not in the work."""
from benchmarks import hybrid_work

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    if "layer_types" not in run.get("dims", {}):
        return None
    return hybrid_work.roofline_pct(run, hybrid_work.GDN_FORWARD, hybrid_work.delta_rule_work(
        run["dims"], hybrid_work.step_tokens(run)))
