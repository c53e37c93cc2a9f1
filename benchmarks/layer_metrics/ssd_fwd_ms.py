"""Device time of the forward state-space scan kernels (Mosaic calls whose
name holds ``ssd_fwd``: the chunked scan of the Mamba-2 mixers, once a layer
and step), per traced step, mean over chips. The convolution and the gated
norm around it are ``conv_silu_*`` / ``gated_norm_*`` and are not in it."""
from benchmarks import kernel_work, ssm_work

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    return kernel_work.kernel_ms(run, ssm_work.SCAN_FORWARD)
