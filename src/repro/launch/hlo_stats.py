"""Parse compiled HLO text for collective traffic (roofline collective term).

``compiled.as_text()`` is the post-SPMD-partitioning optimized HLO; every
cross-device transfer appears as one of:
  all-gather(-start), all-reduce(-start), reduce-scatter, all-to-all,
  collective-permute(-start)

For each op we parse the RESULT shape/dtype and the replica group size,
then convert to *wire bytes per device* with the standard ring formulas:

  all-gather:         result * (g-1)/g        (result = gathered tensor)
  reduce-scatter:     result * (g-1)          (operand = result * g)
  all-reduce:         2 * result * (g-1)/g    (ring RS + AG)
  all-to-all:         result * (g-1)/g
  collective-permute: result                  (point-to-point)

These are per-participating-device send volumes, which is what the ICI
link-bandwidth roofline term wants.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  %all-gather.3 = bf16[16,1024,512]{2,1,0} all-gather(%param.1), ...
_OP_RE = re.compile(
    r"=\s*(?:\()?\s*([a-z0-9]+)\[([0-9,]*)\][^\s]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

# tuple results:  = (bf16[8,128]{...}, bf16[8,128]{...}) all-reduce-start(
_TUPLE_RE = re.compile(
    r"=\s*\(([^)]*)\)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_PAIRS_RE = re.compile(r"source_target_pairs=\{(.*?)\}\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        first = [x for x in m.group(1).split(",") if x.strip() != ""]
        return max(1, len(first))
    return 2  # collective-permute etc.: pairwise


@dataclasses.dataclass
class CollectiveStats:
    wire_bytes: float = 0.0
    by_kind: dict = dataclasses.field(
        default_factory=lambda: defaultdict(float))
    counts: dict = dataclasses.field(
        default_factory=lambda: defaultdict(int))
    per_op: list = dataclasses.field(default_factory=list)
    # (kind, wire_bytes) per op in program order — lets callers separate
    # payload-sized boundary permutes from word-sized RNG-key exchanges
    # (the 2D-mesh wire gates key on this split)

    def as_dict(self) -> dict:
        return {"wire_bytes": self.wire_bytes,
                "by_kind": dict(self.by_kind),
                "counts": dict(self.counts),
                "per_op": list(self.per_op)}


def _wire_bytes(kind: str, result_bytes: int, g: int) -> float:
    g = max(g, 1)
    if kind == "all-gather":
        return result_bytes * (g - 1) / g
    if kind == "reduce-scatter":
        return result_bytes * (g - 1)
    if kind == "all-reduce":
        return 2.0 * result_bytes * (g - 1) / g
    if kind == "all-to-all":
        return result_bytes * (g - 1) / g
    if kind == "collective-permute":
        return float(result_bytes)
    return float(result_bytes)


def _parse_collective_line(line: str) -> tuple[str, float] | None:
    if not any(k in line for k in _COLLECTIVES):
        return None
    if "-done(" in line:          # *-done ops carry no new traffic
        return None
    kind = None
    rbytes = 0
    m = _OP_RE.search(line)
    if m:
        kind = m.group(3)
        rbytes = _shape_bytes(m.group(1), m.group(2))
    else:
        mt = _TUPLE_RE.search(line)
        if mt:
            kind = mt.group(2)
            # tuple result: take the LARGEST element (for *-start the tuple
            # repeats operand/result aliases; avoid double counting)
            sizes = [_shape_bytes(d, s)
                     for d, s in _SHAPE_RE.findall(mt.group(1))]
            rbytes = max(sizes) if sizes else 0
    if kind is None:
        return None
    return kind, _wire_bytes(kind, rbytes, _group_size(line))


def collect_collectives(hlo_text: str) -> CollectiveStats:
    """Flat pass: every collective op counted ONCE (XLA cost_analysis
    semantics — loop bodies NOT multiplied). See collect_collectives_looped
    for trip-count-aware accounting."""
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        parsed = _parse_collective_line(line)
        if parsed is None:
            continue
        kind, wb = parsed
        stats.wire_bytes += wb
        stats.by_kind[kind] += wb
        stats.counts[kind] += 1
        stats.per_op.append((kind, wb))
    return stats


def jaxpr_eqns(jaxpr):
    """Every equation of a (closed) jaxpr, sub-jaxprs (scan, cond,
    shard_map, pjit bodies) included, in program order."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    yield from jaxpr_eqns(sub)


def pallas_kernel_names(jaxpr) -> list[str]:
    """The ``name`` of every ``pallas_call`` in ``jaxpr`` (see
    :func:`jaxpr_eqns`), in program order — the kernels a program runs."""
    return [e.params["name"] for e in jaxpr_eqns(jaxpr)
            if e.primitive.name == "pallas_call"]


def traced_flops(fn, *args) -> float:
    """Scan-aware FLOP count of ``fn(*args)`` (args may be arrays or
    ShapeDtypeStructs). Thin forwarding of ``cost_model.structural_costs``
    so compute-skip assertions live next to the other HLO accounting —
    e.g. gating inactive clients' local SGD out of the round step must
    show up here as a ~k/m FLOP reduction."""
    from .cost_model import structural_costs
    return structural_costs(fn, *args).flops


# ---------------------------------------------------------------------------
# Loop-aware accounting: multiply while-body collectives by trip counts
# ---------------------------------------------------------------------------

_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->")
_CALLEE_RE = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_WHILE_RE = re.compile(
    r"while\(.*?body=%?([\w.\-]+).*?condition=%?([\w.\-]+)|"
    r"while\(.*?condition=%?([\w.\-]+).*?body=%?([\w.\-]+)", re.DOTALL)
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _split_computations(hlo_text: str) -> dict[str, list[str]]:
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if not line.startswith(" ") and "->" in line and "{" in line:
            m = _COMP_HDR_RE.match(stripped.lstrip("%"))
            if m:
                cur = m.group(1)
                comps[cur] = []
                continue
        if cur is not None:
            if stripped == "}":
                cur = None
            else:
                comps[cur].append(line)
    return comps


def _trip_count(cond_lines: list[str]) -> int:
    consts = [int(m.group(1)) for ln in cond_lines
              for m in _CONST_RE.finditer(ln)]
    return max(consts) if consts else 1


def collect_collectives_looped(hlo_text: str) -> CollectiveStats:
    """Trip-count-aware: a collective inside a while body (our lax.scans —
    layer stacks, K local steps, KV-chunk streaming) counts trip_count
    times. Trip counts are read from the loop-condition constants."""
    comps = _split_computations(hlo_text)

    memo: dict[str, CollectiveStats] = {}

    def eval_comp(name: str, depth: int = 0) -> CollectiveStats:
        if name in memo:
            return memo[name]
        memo[name] = CollectiveStats()       # break cycles defensively
        stats = CollectiveStats()
        for line in comps.get(name, []):
            parsed = _parse_collective_line(line)
            if parsed is not None:
                kind, wb = parsed
                stats.wire_bytes += wb
                stats.by_kind[kind] += wb
                stats.counts[kind] += 1
            if depth > 64:
                continue
            if " while(" in line or "= while(" in line.replace("  ", " "):
                mw = _WHILE_RE.search(line)
                if mw:
                    body = mw.group(1) or mw.group(4)
                    cond = mw.group(2) or mw.group(3)
                    tc = _trip_count(comps.get(cond, []))
                    sub = eval_comp(body, depth + 1)
                    stats.wire_bytes += sub.wire_bytes * tc
                    for k, v in sub.by_kind.items():
                        stats.by_kind[k] += v * tc
                    for k, v in sub.counts.items():
                        stats.counts[k] += v * tc
                    continue
            for mc in _CALLEE_RE.finditer(line):
                callee = mc.group(1)
                if callee == name or callee not in comps:
                    continue
                if "condition=" in mc.group(0) or "body=" in mc.group(0):
                    continue    # handled by the while branch above
                sub = eval_comp(callee, depth + 1)
                stats.wire_bytes += sub.wire_bytes
                for k, v in sub.by_kind.items():
                    stats.by_kind[k] += v
                for k, v in sub.counts.items():
                    stats.counts[k] += v
        memo[name] = stats
        return stats

    entry = None
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_HDR_RE.match(line[len("ENTRY"):].strip().lstrip("%"))
            if m:
                entry = m.group(1)
                break
    if entry is None:
        return collect_collectives(hlo_text)
    return eval_comp(entry)
