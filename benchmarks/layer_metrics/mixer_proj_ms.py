"""Device time of the hybrid decoder's mixer projections and their gradients
(``mix/proj_in``, ``mix/proj_out``; the latent mixer's ``mla/down`` and ``mla/up``
are its ``proj_in``), per traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "blocks"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ("mix/proj_in", "mix/proj_out", "mla/down", "mla/up")


def read(run):
    return scope_work.scope_ms(run, SPANS)
