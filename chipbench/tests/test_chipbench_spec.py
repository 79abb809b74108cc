"""``BENCHMARK.json`` resolves by name to the files of each cell, and
keeps to the limits of its own format (CPU)."""
import json
import re
from pathlib import Path

import pytest

import spec

ROOT = Path(spec.ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.load()
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["chipbench"]
    assert all(not w.startswith("/") and ".." not in w
               for w in BENCH["command"])


def test_names_units_and_keys():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/")
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["traffic"]]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert all(NAME.match(n) for n in names)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(CELLS) // 2)


def test_end_to_end_bounds():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert set(e2e) == {"tokens_per_s_per_chip", "setup_s"}
    assert e2e["setup_s"]["bound"] == 0.25
    assert 0.01 <= e2e["tokens_per_s_per_chip"]["bound"] <= 0.25


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = spec.Cell(BENCH, cell)
    conf = json.loads((ROOT / c.config_entry["file"]).read_text())
    assert conf["name"] == c.config_entry["name"]
    assert conf["reduced"] == c.config_entry["reduced"]
    for key in conf["reduced"]:
        assert conf["model"][key] != conf["published"][key]
    assert c.reference().init and c.reference().loss
    assert c.chips * c.traffic["clients_per_shard"] == c.traffic["clients"]
    assert c.tokens_per_round() > 0
    assert c.train_args()[-4:] == ["--rounds", "1", "--seed", "0"]
    assert set(c.limits) == {"loss1", "loss2", "loss3", "change1",
                             "change3", "spread3"}
    for m in c.per_layer:
        assert m["moves"] == "tokens_per_s_per_chip"
        assert callable(c.reader(m["name"]))
    assert [m["name"] for m in c.end_to_end] == ["tokens_per_s_per_chip",
                                                "setup_s"]


def test_metric_lists_only_name_cells():
    for m in BENCH["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_unknown_cell_is_refused():
    with pytest.raises(SystemExit):
        spec.Cell(BENCH, "no-such-cell")
