"""``unscoped_ms`` for the ``bailing_hybrid`` cell: device time of the operations
under none of the program's spans (copies, slices, the closing norm), per
traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "trainer step"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    return scope_work.scope_ms(run, (scope_work.NO_SCOPE,))
