"""Share of the traced window in which no operation ran on the chip:
1 - (union of the device's operation intervals) / window, mean over chips."""

LAYER = "device"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    trace = run.get("trace")
    if not trace or not trace["chips"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
