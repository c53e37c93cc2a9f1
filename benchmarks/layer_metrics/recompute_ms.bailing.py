"""``recompute_ms`` for the ``bailing_hybrid`` cell: device time of what the step
computes a second time (every operation whose scope path holds
``rematted_computation``: the halves of every block but what their policies keep
by name), per traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "trainer step"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    return scope_work.phase_ms(run, "recompute")
