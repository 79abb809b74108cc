"""Share of the traced window in which no op ran on the device (mean
over the cell's chips)."""


def read(trace, ctx):
    return 1.0 - trace.busy_s / trace.window_s
