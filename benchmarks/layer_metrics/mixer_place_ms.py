"""Device time of what stands between a mixer's projections and its kernel and
after it (``mix/place``: per-head norms, rotary, the gates, ``beta`` / ``g``; the
kernels stand outside it), per traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "blocks"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ("mix/place",)


def read(run):
    return scope_work.scope_ms(run, SPANS)
