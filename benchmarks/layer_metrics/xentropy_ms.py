"""Device time of the cross-entropy statistics kernel (Mosaic calls whose
name holds ``xentropy``), per traced step, mean over chips. The XLA fusions
of the unembedding around it are not in it."""
from benchmarks import kernel_work

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    return kernel_work.kernel_ms(run, kernel_work.CROSS_ENTROPY)
