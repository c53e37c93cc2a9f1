"""The backward per-channel delta-rule kernels' share of their roofline: the least
time the chip could take for the rule the backward pass requires
(``bailing_work.rule_work``: the chunked form at 64 tokens with the causal half
of the in-chunk scores, the solve's products and the three state products; q,
k, v, g, beta, o and the entry states once each) over ``kda_bwd_ms``."""
from benchmarks import bailing_work

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    return bailing_work.rule_roofline_pct(run, backward=True)
