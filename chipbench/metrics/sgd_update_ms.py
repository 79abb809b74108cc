"""Device time per round of the ops under ``sgd/update`` (the heavy-ball
update of every local step, apart from the gradient), mean over the
cell's chips; nothing where the program has no such scope. A fusion
carries the scope of its root, so an update XLA fuses into a gradient
op counts there."""


def read(trace, ctx):
    per_chip = trace.scope_s("sgd/update")
    t = sum(per_chip) / len(per_chip)
    return 1e3 * t / ctx["rounds"] if t > 0 else None
