"""The share of the tokens that a looped stack's gate sends through all its
walks: the last entry of the step's ``exit_mass`` counter (the mean over the
tokens of the exit distribution), mean over the window's steps. ``None``
where the adapter hands no such counter."""

LAYER = "trainer step"
UNIT = "share"
MOVES = "train_tokens_per_s"


def read(run):
    mass = run.get("exit_mass")
    if mass is None or not len(mass):
        return None
    return float(mass[:, -1].mean())
