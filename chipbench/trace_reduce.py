"""From a profiler trace of the window to the numbers the per-layer
metrics read.

The JAX profiler writes one ``.xplane.pb`` per run. Its device planes
(``/device:TPU:<i>``) hold a line of XLA ops, each event one HLO
instruction with its start and duration on the host's clock and a
``tf_op`` stat: the ``op_name`` metadata, the path of ``jax.named_scope``
names under which the instruction was traced. The host plane holds the
benchmark's own spans (``bench/data``, ``bench/dispatch``,
``bench/wait``), written by ``jax.profiler.TraceAnnotation``.

Reductions, per chip:

* busy time: the union of the op intervals inside the window (the
  first to the last benchmark span);
* a scope's device time: the union of the intervals of ops whose
  ``tf_op`` path holds that scope;
* exposed collective time: the part of the collective ops' union that no
  other op covers;
* idle gaps: the holes in the busy union, each labelled by the host
  span that was open at its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
SCOPE_STAT = "tf_op"
HOST_PREFIX = "bench/"
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "all-to-all", "reduce-scatter", "send", "recv")


def union(intervals):
    """Sorted, merged [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """Parts of merged ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class Op:
    start: int
    end: int
    name: str           # the HLO instruction's name
    scope: str          # its op_name metadata

    def in_scope(self, scope: str) -> bool:
        return f"/{scope}/" in f"/{self.scope}/"

    @property
    def collective(self) -> bool:
        return self.name.startswith(COLLECTIVES)


@dataclasses.dataclass
class Reduced:
    """A trace reduced to what the metric readers need. Times in ns."""

    chips: list            # [[Op, ...] per chip], sorted by start
    spans: list            # [(name, start, end)] host benchmark spans
    lo: int                # window start
    hi: int                # window end

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def _busy(self, ops):
        return union(clip([(o.start, o.end) for o in ops], self.lo,
                          self.hi))

    @property
    def busy_s(self) -> float:
        """Mean over chips of the time some op ran."""
        return sum(length(self._busy(ops)) for ops in self.chips) \
            / len(self.chips) * 1e-9

    def scope_s(self, *scopes) -> list:
        """Per chip, seconds in which an op under any of ``scopes`` ran."""
        return [length(self._busy([o for o in ops
                                   if any(o.in_scope(s) for s in scopes)]))
                * 1e-9 for ops in self.chips]

    def collective_exposed_s(self) -> list | None:
        """Per chip, seconds of collective ops that no other op covers;
        None where the trace holds no collective."""
        if not any(o.collective for ops in self.chips for o in ops):
            return None
        out = []
        for ops in self.chips:
            coll = self._busy([o for o in ops if o.collective])
            other = self._busy([o for o in ops if not o.collective])
            out.append(length(subtract(coll, other)) * 1e-9)
        return out

    def _label(self, t: int) -> str:
        for name, s, e in self.spans:
            if s <= t < e:
                return name
        return "host:none"

    def self_ns(self, chip: int = 0) -> list:
        """[(op, ns)]: each op's time inside the window less the ops
        nested in it (a while loop holds its body's ops)."""
        ops = sorted(self.chips[chip], key=lambda o: (o.start, -o.end))
        own = [max(0, min(o.end, self.hi) - max(o.start, self.lo))
               for o in ops]
        stack = []
        for i, o in enumerate(ops):
            while stack and ops[stack[-1]].end <= o.start:
                stack.pop()
            if stack and o.end <= ops[stack[-1]].end:
                own[stack[-1]] -= own[i]
            stack.append(i)
        return list(zip(ops, own))

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (chip 0, self time, by
        instruction and scope), and the longest idle gaps with the host
        span open in each."""
        ops = self.chips[0]
        by_name = {}
        for o, ns in self.self_ns(0):
            if ns > 0:
                key = f"{o.name} @ {o.scope}" if o.scope else o.name
                by_name[key] = by_name.get(key, 0) + ns
        dev = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = subtract([(self.lo, self.hi)], self._busy(ops))
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[k, v * 1e-9] for k, v in dev],
                "idle_gaps": [[self._label((s + e) // 2), (e - s) * 1e-9]
                              for s, e in gaps]}


def _stat(event, name: str) -> str:
    for k, v in event.stats:
        if k == name:
            return str(v)
    return ""


def hlo_scopes(hlo_text: str) -> dict:
    """Instruction name -> ``op_name`` metadata, from a compiled
    program's HLO text: the scope of a device event whose own stats do
    not carry it."""
    out = {}
    for m in _HLO_OP.finditer(hlo_text):
        out[m.group(1)] = m.group(2)
    return out


_HLO_OP = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"',
                     re.M)


def reduce_profile(pd, n_chips: int, scopes: dict | None = None
                   ) -> Reduced:
    """A :class:`Reduced` from ``jax.profiler.ProfileData``; ``scopes``
    (from :func:`hlo_scopes`) names the scope of events whose stats do
    not."""
    scopes = scopes or {}
    chips, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                or plane.name.startswith("/device:CPU"):
            idx = int(plane.name.rsplit(":", 1)[1])
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    # The event's name is the instruction's HLO text.
                    name = ev.name.split(" = ", 1)[0].lstrip("%")
                    scope = _stat(ev, SCOPE_STAT) or scopes.get(name, "")
                    ops.append(Op(int(ev.start_ns), int(ev.end_ns), name,
                                  scope))
            chips[idx] = sorted(ops, key=lambda o: o.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.end_ns)))
    if not spans:
        raise ValueError("trace holds no benchmark host spans")
    spans.sort(key=lambda s: s[1])
    lo, hi = min(s for _, s, _ in spans), max(e for _, _, e in spans)
    used = [chips.get(i, []) for i in range(n_chips)]
    return Reduced(chips=used, spans=spans, lo=lo, hi=hi)


def reduce_file(path: str, n_chips: int, scopes: dict | None = None
                ) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), n_chips, scopes)


def reduce_dir(trace_dir: str, n_chips: int, scopes: dict | None = None
               ) -> Reduced:
    """The one ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one xplane file under {trace_dir}, "
                         f"found {files}")
    return reduce_file(files[0], n_chips, scopes)
