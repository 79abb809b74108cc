"""Device time per round of the ops under the ``round/mix`` scope (wire
encode, permutes and decode-apply), mean over the cell's chips."""


def read(trace, ctx):
    per_chip = trace.scope_s("round/mix")
    t = sum(per_chip) / len(per_chip)
    return 1e3 * t / ctx["rounds"] if t > 0 else None
