"""Expert layer: the busiest held expert's visits over the mean of the held
experts', from the `expert_load` node of the traced calls (`[sparse layers,
experts held]` a row, summed over the calls' rows), in the worst sparse
layer. 1.0 is an even load; the grouped products' time follows the sum, but
in the deployment the busiest expert's chip sets the pace. Lower is better."""


def read(ctx):
    load = ctx["counters"].get("expert_load")
    if not load:
        return None
    worst = None
    for layer in load:
        mean = sum(layer) / len(layer)
        if mean > 0:
            worst = max(worst or 0.0, max(layer) / mean)
    return worst
