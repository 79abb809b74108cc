"""The benchmark's own table of chip peaks (``peaks.json``), keyed by
the ``device_kind`` JAX reports. A kind not in the table is an error."""
from __future__ import annotations

import json
from pathlib import Path


def peaks(kind: str) -> dict:
    with open(Path(__file__).resolve().parent / "peaks.json") as f:
        table = json.load(f)["kinds"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; the table "
                       f"has {sorted(table)}")
    return table[kind]
