"""``recompute_ms`` for the ``ouro`` cell: device time of what the step
computes a second time (every operation whose scope path holds
``rematted_computation``: the blocks of every walk but their flash kernels,
every walk's closing norm, every exit's head and loss), per traced step, mean
over chips."""
from benchmarks import scope_work

LAYER = "trainer step"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    return scope_work.phase_ms(run, "recompute")
