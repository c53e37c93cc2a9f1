"""``moe_rows_ms`` for the ``nemotron_h`` cell: device time of the movements
between tokens and expert-sorted rows (Mosaic calls whose name holds
``moe_rows``; here at rows widened from 2,688 to 4,096), per traced step,
mean over chips. The pad and the slice around them are XLA's and stand in
``moe_block_ms.ssm``."""
from benchmarks import kernel_work

LAYER = "experts (dropless routing)"
UNIT = "ms"
MOVES = "train_tokens_per_s"
EXPERT_ROWS = "moe_rows"


def read(run):
    return kernel_work.kernel_ms(run, EXPERT_ROWS)
