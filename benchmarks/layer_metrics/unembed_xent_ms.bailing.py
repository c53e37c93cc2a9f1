"""``unembed_xent_ms`` for the ``bailing_hybrid`` cell: device time of the
unembedding and the cross-entropy, forward and backward
(``hybrid/unembed_xent``), per traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "trainer step"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ('hybrid/unembed_xent',)


def read(run):
    return scope_work.scope_ms(run, SPANS)
