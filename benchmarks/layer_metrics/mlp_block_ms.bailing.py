"""``mlp_block_ms`` for the ``bailing_hybrid`` cell: device time of its one dense
SwiGLU half (``hybrid/dense``: the norm and the three matrices at 6,144), per
traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "blocks"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ('hybrid/dense',)


def read(run):
    return scope_work.scope_ms(run, SPANS)
