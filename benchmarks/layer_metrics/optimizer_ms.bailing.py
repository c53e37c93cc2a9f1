"""``optimizer_ms`` for the ``bailing_hybrid`` cell: device time of the update
(``amp/apply_master`` with ``fused_adam/update`` fused in, and
``amp/unscale_check``), per traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "trainer step"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ('amp/apply_master', 'fused_adam/update', 'amp/unscale_check')


def read(run):
    return scope_work.scope_ms(run, SPANS)
