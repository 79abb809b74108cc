"""Device time per round of the ops under the ``round/metrics`` scope
(the step's default metrics: the loss mean, the active share and the two
full-model ``consensus_distance`` sums, with their all-reduces across
chips), mean over the cell's chips; nothing where the program has no
such scope."""


def read(trace, ctx):
    per_chip = trace.scope_s("round/metrics")
    t = sum(per_chip) / len(per_chip)
    return 1e3 * t / ctx["rounds"] if t > 0 else None
