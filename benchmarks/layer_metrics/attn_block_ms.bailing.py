"""``attn_block_ms`` for the ``bailing_hybrid`` cell: device time of its one latent-
attention block (everything traced under ``hybrid/attn_mla``: the norm, ``mla/down``,
``mla/up``, the q/k norms and rotary, the flash kernels, the head-wise gate,
``w_o``), per traced step, mean over chips."""
from benchmarks import scope_work

LAYER = "blocks"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SPANS = ('hybrid/attn_mla',)


def read(run):
    return scope_work.scope_ms(run, SPANS)
