"""Device time of what the step computes a second time: every operation whose
scope path holds ``rematted_computation`` (the forward pass of a
``jax.checkpoint`` run again in the backward pass), per traced step, mean over
chips. Nothing on a program that recomputes nothing."""
from benchmarks import scope_work

LAYER = "trainer step"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    return scope_work.phase_ms(run, "recompute")
