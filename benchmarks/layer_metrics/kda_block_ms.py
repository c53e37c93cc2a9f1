"""Device time of the per-channel delta-rule mixer blocks: everything traced
under ``hybrid/kda`` (the norm, the five projections, the convolution, the decay
and the write strength, ``kda_fwd`` / ``kda_bwd``, the gated head norm, ``w_o``),
per traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "blocks"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ('hybrid/kda',)


def read(run):
    return scope_work.scope_ms(run, SPANS)
