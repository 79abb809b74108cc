"""Device time per round of the ops under ``wire/encode/noise``: the
stochastic-rounding noise the encoder draws (per-leaf keys and uniform
draws in planar layout), mean over the cell's chips; nothing where the
program has no such scope."""


def read(trace, ctx):
    per_chip = trace.scope_s("wire/encode/noise")
    t = sum(per_chip) / len(per_chip)
    return 1e3 * t / ctx["rounds"] if t > 0 else None
