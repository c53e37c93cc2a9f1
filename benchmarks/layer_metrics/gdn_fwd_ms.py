"""Device time of the forward gated delta-rule kernels (Mosaic calls whose
name holds ``gdn_fwd``: the recurrence over chunks), per traced step, mean
over chips. The chunk operands are prepared by XLA fusions under the
``hybrid/gdn`` scope and are not in it."""
from benchmarks import hybrid_work, kernel_work

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    return kernel_work.kernel_ms(run, hybrid_work.GDN_FORWARD)
