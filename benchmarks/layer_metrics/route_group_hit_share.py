"""The share of the tokens whose kept routing groups (4 of 8) hold the group of
the experts held here: the step's ``router_group_hit`` counter, mean over the
window's steps and the expert layers, in per cent. A token outside it can send
nothing to this chip. ``None`` where the adapter hands no such counter."""

LAYER = "experts (dropless routing)"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    hit = run.get("router_group_hit")
    if hit is None or not len(hit):
        return None
    return 100.0 * float(hit.mean())
