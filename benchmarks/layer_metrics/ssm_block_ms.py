"""Device time of the state-space mixer blocks (everything traced under
``hybrid/ssm``), per traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "blocks"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ("hybrid/ssm",)


def read(run):
    return scope_work.scope_ms(run, SPANS)
