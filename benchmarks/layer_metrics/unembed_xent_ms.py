"""Device time of the unembedding and the cross-entropy, forward and backward
(``gpt/unembed_xent``, ``hybrid/unembed_xent``; ``xentropy_ms`` is the kernel
inside it), per traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "trainer step"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ("gpt/unembed_xent", "hybrid/unembed_xent")


def read(run):
    return scope_work.scope_ms(run, SPANS)
