"""Device time per round of the ops under the ``round/local_sgd`` scope
(forward, backward and heavy-ball update of every client), mean over
the cell's chips."""


def read(trace, ctx):
    per_chip = trace.scope_s("round/local_sgd")
    t = sum(per_chip) / len(per_chip)
    return 1e3 * t / ctx["rounds"] if t > 0 else None
