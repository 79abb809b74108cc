"""Device time per round of the ops under any Mamba2 mixer scope
(``mamba/proj``, ``mamba/conv``, ``mamba/ssd``, ``mamba/gate``: the
whole mixer, forward and backward, all K steps of every client), mean
over the cell's chips; nothing where the program has no such scope.
The scopes are matched in their plain, ``jvp(...)`` and
``transpose(jvp(...))`` forms, as in ``ssd_ms``."""
import re

import trace_reduce

SCOPE = re.compile(r"(?:^|[/(])mamba/(?:proj|conv|ssd|gate)(?:[/)]|$)")


def read(trace, ctx):
    per_chip = [trace_reduce.length(trace_reduce.union(trace_reduce.clip(
        [(o.start, o.end) for o in ops if SCOPE.search(o.scope)],
        trace.lo, trace.hi))) for ops in trace.chips]
    t = sum(per_chip) / len(per_chip)
    return 1e-6 * t / ctx["rounds"] if t > 0 else None
