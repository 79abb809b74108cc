"""The benchmark as data: ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one cell or one per-layer
metric lives in a file of its own, found by the name in
``BENCHMARK.json``:

* ``chipbench/configs/<config>.json``: the model's sizes as run, the
  program's arguments for them, and the reference module's name;
* ``chipbench/workloads/<cell>.json``: the traffic (clients, local
  steps, batch, sequence, wire bits, topology, learning rate);
* ``chipbench/limits/<cell>.json``: the limit of each number that
  decides ``correct``;
* ``chipbench/metrics/<metric>.py``: the reader of a per-layer metric,
  a function ``read(trace, ctx) -> float | None``.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One cell resolved by name: its entry, configuration, traffic,
    limits and the metrics it reports."""

    def __init__(self, bench: dict, name: str, root: Path = ROOT):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; "
                             f"BENCHMARK.json has {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = _read_json(root / self.config_entry["file"])
        self.model = self.config["model"]
        bench_dir = root / bench["paths"][0]
        self.traffic = _read_json(bench_dir / "workloads" /
                                  f"{self.entry['traffic']}.json")
        lim = bench_dir / "limits" / f"{name}.json"
        self.limits = _read_json(lim) if lim.exists() else {}
        self.metrics_dir = bench_dir / "metrics"
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def reader(self, metric: str):
        """The ``read`` function of ``metrics/<metric>.py``."""
        path = self.metrics_dir / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    def reference(self):
        """The configuration's plain reference module."""
        return importlib.import_module(
            f"references.{self.config['reference']}")

    def tokens_per_round(self) -> int:
        t = self.traffic
        return t["clients"] * t["local_steps"] * t["batch"] * t["seq"]

    def train_args(self, program_seed: int = 0) -> list:
        """``repro.launch.train`` arguments for this cell, one round."""
        t = self.traffic
        return list(self.config["train_args"]) + [
            "--clients", str(t["clients"]),
            "--clients-per-shard", str(t["clients_per_shard"]),
            "--local-steps", str(t["local_steps"]),
            "--batch", str(t["batch"]), "--seq", str(t["seq"]),
            "--bits", str(t["bits"]), "--eta", repr(t["eta"]),
            "--theta", repr(t["theta"]),
            "--self-weight", repr(t["self_weight"]),
            "--mixer-impl", t["mixer"], "--wire", t["wire"],
            "--rounds", "1", "--seed", str(program_seed)]
