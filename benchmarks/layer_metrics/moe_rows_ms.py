"""Device time of the movements between tokens and expert-sorted rows (Mosaic
calls whose name holds ``moe_rows``: the row gather, the weighted combine and
their transposes), per traced step, mean over chips. A program that moves
the rows by XLA gathers names no such call and reports nothing."""
from benchmarks import kernel_work

LAYER = "experts (dropless routing)"
UNIT = "ms"
MOVES = "train_tokens_per_s"
EXPERT_ROWS = "moe_rows"


def read(run):
    return kernel_work.kernel_ms(run, EXPERT_ROWS)
