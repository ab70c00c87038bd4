"""How unevenly the router loads the experts held: per expert layer, the
pairs of the most-loaded held expert over the mean of the held experts,
from the program's routed-pair counter summed over the window; the
largest over the layers."""


def read(ctx):
    pairs = ctx["driver"].counters.get("expert_pairs")
    if pairs is None or not pairs.size or pairs.sum() <= 0:
        return None
    per_layer = pairs.max(axis=1) / pairs.mean(axis=1)
    return float(per_layer.max())
