"""Device time of the operations under none of the program's spans (copies,
slices, the dp cell's ``psum``s), per traced step, mean over chips. A span's
own operations (``amp/fwd_bwd``'s) are not in it."""
from benchmarks import scope_work

LAYER = "trainer step"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    return scope_work.scope_ms(run, (scope_work.NO_SCOPE,))
