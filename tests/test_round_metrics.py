"""The training driver's round step leaves out the full-model diagnostics.

``consensus_dist`` and ``local_drift`` are cross-client means of every
leaf: on a client mesh, an all-reduce of the whole model, twice a round.
``launch/train.py`` builds its step with ``with_metrics=False`` (bar
``--telemetry``) and evaluates ``consensus_dist`` from the returned
params on the rounds whose record it writes. These tests pin that:

* the driver trains bitwise as the step built ``with_metrics=True``
  (``make_round_step``'s default), logs the same losses and cheap scalars, and its
  ``consensus_dist`` equals the in-step value to 1e-6 relative: the
  4-client sparse ring at tiny OLMo size with the q8 stochastic wire,
  a scheduled (partial participation) and an async run on 4 host
  devices;
* the driver's compiled step has no all-reduce under ``round/metrics``
  that carries more than a scalar, and the ``--telemetry`` step keeps
  the full-leaf ones;
* the diagnostic runs on the cadence rounds only (11 of 20), or on
  every round when a JSONL log is open, and the driver says how often.

The 4-device runs share one subprocess (the main test process keeps
seeing one device), with the persistent compilation cache off: a cached
executable keeps the metadata of the program that compiled it.
"""
import json
import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CASES = ("ring", "scheduled", "async")

_RUN = """
    import json, os, re, tempfile
    import jax
    import numpy as np
    from repro.launch import train

    RING = ["--arch", "olmo-1b", "--layers", "1", "--clients", "4",
            "--clients-per-shard", "1", "--local-steps", "2",
            "--batch", "1", "--seq", "16", "--bits", "8", "--eta", "0.03",
            "--theta", "0.9", "--self-weight", "0.5",
            "--mixer-impl", "sparse", "--wire", "auto", "--rounds", "3"]
    CASES = {"ring": [],
             "scheduled": ["--schedule", "partial", "--p-active", "0.6"],
             "async": ["--async-gossip"]}
    make = train.make_round_step

    def with_sums(*a, **k):
        return make(*a, **{**k, "with_metrics": True})

    def run(args, in_step):
        train.make_round_step = with_sums if in_step else make
        path = os.path.join(tempfile.mkdtemp(), "run.jsonl")
        try:
            res = train.main(args + ["--log-jsonl", path])
        finally:
            train.make_round_step = make
        with open(path) as f:
            recs = [json.loads(l) for l in f]
        return res, {
            "params": [np.asarray(p).tobytes().hex()
                       for p in jax.tree.leaves(res.state.params)],
            "rounds": [{k: v for k, v in r.items()
                        if k not in ("wall_s", "round_s")}
                       for r in recs if r["kind"] == "round"],
            "info": [r["msg"] for r in recs if r["kind"] == "info"],
            "step_keys": sorted(res.metrics)}

    def all_reduces(res):
        txt = res.step.lower(res.state, res.batches).compile().as_text()
        out = []
        for line in txt.splitlines():
            m = re.search(r" = (.*?) all-reduce(?:-start)?\\(", line)
            if m:
                name = re.search(r'op_name="([^"]*)"', line)
                out.append([m.group(1), name.group(1) if name else ""])
        return out

    out = {}
    for case, extra in CASES.items():
        res, mine = run(RING + extra, in_step=False)
        _, theirs = run(RING + extra, in_step=True)
        out[case] = {"driver": mine, "in_step": theirs}
        if case == "ring":
            out["hlo_driver"] = all_reduces(res)
    res, _ = run(RING + ["--rounds", "1", "--telemetry"], in_step=False)
    out["hlo_telemetry"] = all_reduces(res)
    print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(_RUN)],
                       capture_output=True, text=True, timeout=900, env=env,
                       cwd=REPO)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    line, = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("case", CASES)
def test_driver_step_trains_as_the_in_step_metrics_step(four_devices, case):
    mine = four_devices[case]["driver"]
    theirs = four_devices[case]["in_step"]
    # Same parameters, bit for bit, after 3 rounds.
    assert mine["params"] == theirs["params"]
    assert [r["t"] for r in mine["rounds"]] == [0, 1, 2]
    assert "local_drift" not in mine["step_keys"]
    if case != "async":  # the async step never emits local_drift
        assert "local_drift" in theirs["step_keys"]
    cheap = {"scheduled": {"active_frac"},
             "async": {"mean_staleness", "max_staleness", "clock",
                       "ready_frac", "live_edges"}}.get(case, set())
    for a, b in zip(mine["rounds"], theirs["rounds"]):
        assert set(a) == set(b)
        assert cheap | {"loss", "consensus_dist"} <= set(a)
        for k in set(a) - {"consensus_dist"}:
            assert a[k] == b[k], (k, a[k], b[k])
        assert a["consensus_dist"] > 0
        np.testing.assert_allclose(a["consensus_dist"], b["consensus_dist"],
                                   rtol=1e-6, atol=0)
    assert "consensus diagnostic on 3 of 3 rounds" in mine["info"]


def _shapes(hlo_type: str) -> list:
    return re.findall(r"[a-z]+[0-9]*\[([^\]]*)\]", hlo_type)


def test_driver_step_all_reduces_no_more_than_a_scalar_for_metrics(
        four_devices):
    ops = four_devices["hlo_driver"]
    metrics = [t for t, name in ops if "round/metrics" in name]
    # The loss mean is a cross-client sum of one scalar.
    assert metrics, ops
    assert all(dims == "" for t in metrics for dims in _shapes(t)), metrics


def test_telemetry_step_keeps_the_full_model_sums(four_devices):
    ops = four_devices["hlo_telemetry"]
    full = [t for t, name in ops if "round/metrics" in name
            and any(dims for dims in _shapes(t))]
    # One per parameter leaf and sum at least: x' and z, every leaf.
    assert len(full) >= 2, ops


def _train(*extra, in_step=False):
    from repro.launch import train
    args = ["--arch", "olmo-1b", "--layers", "1", "--clients", "3",
            "--local-steps", "1", "--batch", "1", "--seq", "8",
            "--mixer-impl", "dense", "--rounds", "20", *extra]
    make = train.make_round_step
    if in_step:
        train.make_round_step = (
            lambda *a, **k: make(*a, **{**k, "with_metrics": True}))
    try:
        return train.main(args)
    finally:
        train.make_round_step = make


def test_consensus_runs_on_the_cadence_rounds_only(capsys, tmp_path):
    res = _train()
    said = capsys.readouterr().out
    theirs = _train(in_step=True)
    capsys.readouterr()
    logged = [r["t"] for r in res.rounds]
    assert logged == [0, 2, 4, 6, 8, 10, 12, 14, 16, 18, 19]
    assert logged == [r["t"] for r in theirs.rounds]
    for a, b in zip(res.rounds, theirs.rounds):
        assert a["loss"] == b["loss"]
        assert a["consensus_dist"] > 0
        np.testing.assert_allclose(a["consensus_dist"], b["consensus_dist"],
                                   rtol=1e-6, atol=0)
    # The last round's value is what the run hands back.
    np.testing.assert_allclose(float(res.metrics["consensus_dist"]),
                               res.rounds[-1]["consensus_dist"], rtol=0)
    assert "consensus diagnostic on 11 of 20 rounds" in said.splitlines()

    _train("--log-jsonl", str(tmp_path / "run.jsonl"))
    assert ("consensus diagnostic on 20 of 20 rounds"
            in capsys.readouterr().out.splitlines())
