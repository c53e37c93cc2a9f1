"""Device time of the delta-rule mixer blocks (everything traced under
``hybrid/gdn``), per traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "blocks"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ("hybrid/gdn",)


def read(run):
    return scope_work.scope_ms(run, SPANS)
