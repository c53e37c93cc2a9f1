"""Device time of collective operations during which no compute operation
runs on that chip, per traced step, mean over chips."""

LAYER = "parallel (dp pmean)"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    trace = run.get("trace")
    if not trace or not trace["chips"] or not run.get("step_s") or run["chips"] < 2:
        return None
    return 1e3 * trace["exposed_collective_s"] / len(run["step_s"])
