"""Device time per round of the mixer's buffer work around the codec:
the union of the ops under ``wire/pack`` (planar buffers from the
parameter leaves), ``wire/scales`` (per-leaf quantizer steps),
``wire/stream`` (stream assembly and its split after the exchange) and
``wire/unpack`` (leaves from the mixed buffer), mean over the cell's
chips; nothing where the program has none of these scopes."""


def read(trace, ctx):
    per_chip = trace.scope_s("wire/pack", "wire/scales", "wire/stream",
                             "wire/unpack")
    t = sum(per_chip) / len(per_chip)
    return 1e3 * t / ctx["rounds"] if t > 0 else None
