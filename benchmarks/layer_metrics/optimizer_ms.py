"""Device time of the update: the master apply with Adam's arithmetic fused in
(``amp/apply_master``, ``fused_adam/update``: XLA fuses them, so they are read
together) and the unscale and finite check (``amp/unscale_check``), per
traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "trainer step"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ("amp/apply_master", "fused_adam/update", "amp/unscale_check")


def read(run):
    return scope_work.scope_ms(run, SPANS)
