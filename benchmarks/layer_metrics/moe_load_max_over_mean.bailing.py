"""``moe_load_max_over_mean`` for the ``bailing_hybrid`` cell: largest over mean of
the assignments to the experts held, per expert layer and step, mean over
the window's steps and the layers: 1.0 is an even load."""

LAYER = "experts (dropless routing)"
UNIT = "x"
MOVES = "train_tokens_per_s"


def read(run):
    loads = run.get("expert_load")
    if loads is None or not len(loads):
        return None
    mean = loads.mean(axis=-1)
    return float((loads.max(axis=-1) / mean.clip(min=1e-9)).mean())
