"""Device time of the kernels around the delta rule (Mosaic calls whose name
holds ``conv_silu`` or ``gated_norm``: the depthwise causal convolution +
SiLU and the gated RMS norm of ``ops/pallas/delta_mixer.py``, forward and
backward), per traced step, mean over chips."""
from benchmarks import kernel_work

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s"
PARTS = ("conv_silu", "gated_norm")


def read(run):
    found = [ms for ms in (kernel_work.kernel_ms(run, part) for part in PARTS) if ms is not None]
    return sum(found) if found else None
