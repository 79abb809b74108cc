"""Device time per round of the ops under the Mamba2 SSD scope
(``mamba/ssd``: the chunked state-space scan and its D skip, forward and
backward, all K steps of every client), mean over the cell's chips;
nothing where the program has no such scope.

Inside a gradient JAX renames a scope opened in the differentiated
function: ``jvp(mamba/ssd)`` in the forward pass and
``transpose(jvp(mamba/ssd))`` in the backward. Where a scan or a remat
closes over the layer, the scope keeps its plain ``/mamba/ssd/`` form
after the transform's name. All three forms count."""
import re

import trace_reduce

SCOPE = re.compile(r"(?:^|[/(])mamba/ssd(?:[/)]|$)")


def read(trace, ctx):
    per_chip = [trace_reduce.length(trace_reduce.union(trace_reduce.clip(
        [(o.start, o.end) for o in ops if SCOPE.search(o.scope)],
        trace.lo, trace.hi))) for ops in trace.chips]
    t = sum(per_chip) / len(per_chip)
    return 1e-6 * t / ctx["rounds"] if t > 0 else None
