"""Device time of the forward gated delta-rule kernels (Mosaic calls whose
name holds ``gdn_fwd``; since PR 27 the whole rule from ``q, k, v, g, beta``:
the norms, the chunk operands, the 64 x 64 inverse and the recurrence), per
traced step, mean over chips. The convolution and the gated norm around it
are ``delta_mixer_ms``'s."""
from benchmarks import hybrid_work, kernel_work

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    return kernel_work.kernel_ms(run, hybrid_work.GDN_FORWARD)
