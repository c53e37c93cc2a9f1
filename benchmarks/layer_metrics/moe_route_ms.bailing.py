"""``moe_route_ms`` for the ``bailing_hybrid`` cell: device time of the expert
layers' routing (``moe/route``: the scores, the groups kept, the top 8 inside
them, the plan), per traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "experts (dropless routing)"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ('moe/route',)


def read(run):
    return scope_work.scope_ms(run, SPANS)
