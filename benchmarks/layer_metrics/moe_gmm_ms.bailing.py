"""``moe_gmm_ms`` for the ``bailing_hybrid`` cell: device time of the grouped expert
products (Mosaic calls whose name holds ``moe_gmm``: forward, dx and dw
together), per traced step, mean over chips."""
from benchmarks import hybrid_work, kernel_work

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    return kernel_work.kernel_ms(run, hybrid_work.EXPERT_MATMUL)
