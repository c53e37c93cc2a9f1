"""Device time of the expert layers' routing (``moe/route``: scores, top-k, the
sort into rows, the token-ordered lists, the counts), per traced step, mean
over chips."""
from benchmarks import scope_work

LAYER = "experts (dropless routing)"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ("moe/route",)


def read(run):
    return scope_work.scope_ms(run, SPANS)
