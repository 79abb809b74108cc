"""Model FLOP/s utilization of the whole round step, in percent: the
training FLOPs the window's tokens require (``chipbench/flops.py``, from
the configuration's shapes) over the traced window times the chips
times the chip's bf16 peak."""
import flops
from peaks import peaks


def read(trace, ctx):
    need = flops.train_flops_per_token(ctx["model"], ctx["traffic"]["seq"])
    peak = peaks(ctx["kind"])["bf16_flops_per_s"]
    return 100.0 * need * ctx["tokens"] / (trace.window_s * ctx["chips"]
                                           * peak)
