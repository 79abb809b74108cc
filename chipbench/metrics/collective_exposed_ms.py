"""Per round, the collective ops' device time during which no other op
runs on that chip, the most over the cell's chips; nothing when the
trace holds no collective."""


def read(trace, ctx):
    per_chip = trace.collective_exposed_s()
    if per_chip is None:
        return None
    return 1e3 * max(per_chip) / ctx["rounds"]
