"""Roofline table: reads experiments/dryrun/*.json produced by
repro.launch.dryrun and emits one row per (arch x shape x mesh x tag)."""
import json
from pathlib import Path

OUT = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"


def run():
    rows = []
    if not OUT.exists():
        return [("roofline/no-dryrun-data", 0.0,
                 "run: python -m repro.launch.dryrun")]
    for f in sorted(OUT.glob("*.json")):
        rec = json.loads(f.read_text())
        name = f"roofline/{rec['arch']}/{rec['shape']}/{rec['mesh']}/" \
               f"{rec.get('tag', 'baseline')}"
        if rec.get("skipped"):
            rows.append((name, 0.0, "skipped=" + rec["skipped"][:40]))
            continue
        t = rec["roofline"]
        rows.append((name, t[rec["dominant"]] * 1e6,
                     f"dom={rec['dominant'][:-2]};"
                     f"c={t['compute_s']*1e3:.1f}ms;"
                     f"m={t['memory_s']*1e3:.1f}ms;"
                     f"n={t['collective_s']*1e3:.1f}ms;"
                     f"useful={rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'],2)}"))
    return rows
