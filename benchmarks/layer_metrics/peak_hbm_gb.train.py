"""``memory_stats()["peak_bytes_in_use"]`` on the fullest chip after the
window, before the reference runs."""

LAYER = "device"
UNIT = "GB"
MOVES = "train_tokens_per_s"


def read(run):
    return run["memory_peak_bytes"] / 1e9
