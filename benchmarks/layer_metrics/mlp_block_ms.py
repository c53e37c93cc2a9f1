"""Device time of the dense feed-forward blocks (``gpt/mlp``, ``hybrid/dense``),
per traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "blocks"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ("gpt/mlp", "hybrid/dense")


def read(run):
    return scope_work.scope_ms(run, SPANS)
