"""``mlp_block_ms`` for the ``ouro`` cell: device time of its SwiGLU halves,
every layer once a walk (``hybrid/dense``: the two norms and the three
matrices), per traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "blocks"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ("hybrid/dense",)


def read(run):
    return scope_work.scope_ms(run, SPANS)
