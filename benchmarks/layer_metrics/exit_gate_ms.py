"""Device time of a looped stack's exit gate: the gate's logit after every
walk but the last, the exit distribution and the weighting of the exits'
losses with the entropy term, forward and backward (``hybrid/exit``), per
traced step, mean over chips. Nothing on a program whose stack cannot loop."""
from benchmarks import scope_work

LAYER = "trainer step"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ("hybrid/exit",)


def read(run):
    return scope_work.scope_ms(run, SPANS)
