"""Median host-clock time of one training step that ends in a fetched loss
(the traced steps, one at a time)."""
import statistics

LAYER = "trainer step"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    if not run.get("step_s"):
        return None
    return 1e3 * statistics.median(run["step_s"])
