"""Device time per round during which only ops under no ``round/<stage>``
scope run: the union of those ops' intervals less the union of the
scoped ops' (a loop under a stage covers the ops of its body), mean
over the cell's chips."""
import re

import trace_reduce

STAGE = re.compile(r"(?:^|/)round/[^/]+")


def read(trace, ctx):
    out = []
    for ops in trace.chips:
        spans = ([], [])
        for o in ops:
            spans[bool(STAGE.search(o.scope))].append((o.start, o.end))
        free, staged = (trace_reduce.union(trace_reduce.clip(
            s, trace.lo, trace.hi)) for s in spans)
        out.append(trace_reduce.length(trace_reduce.subtract(free, staged)))
    return 1e-6 * sum(out) / len(out) / ctx["rounds"]
