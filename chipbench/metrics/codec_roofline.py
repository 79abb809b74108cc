"""Share of its memory roofline the b-bit gossip codec reaches, in
percent: the least bytes the codec must move per round on a chip
(``flops.codec_least_bytes`` for each of the chip's clients) over the
chip's HBM bandwidth, divided by the device time under the
``wire/encode`` and ``wire/decode`` scopes, whatever ops implement them.
Mean over the cell's chips; nothing when the trace has no codec ops."""
import check
import flops
from peaks import peaks


def read(trace, ctx):
    t = ctx["traffic"]
    if t["bits"] >= 32:
        return None
    per_chip = trace.scope_s("wire/encode", "wire/decode")
    spent = sum(per_chip) / len(per_chip) / ctx["rounds"]
    if spent <= 0:
        return None
    W = check.mixing_matrix(t)
    streams = int((W[0] != 0).sum())
    least = t["clients_per_shard"] * flops.codec_least_bytes(
        ctx["model"], t["bits"], streams)
    return 100.0 * least / peaks(ctx["kind"])["hbm_bytes_per_s"] / spent
