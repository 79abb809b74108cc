"""Every device op of the compiled round step runs under a named stage.

The chip benchmark reads per-layer times from a device trace through the
``op_name`` metadata of the compiled round step (``jax.named_scope``
paths). These tests compile the step of a tiny OLMo on a 2-shard CPU mesh
(4 clients, 2 per shard: both intra-shard lane gathers and boundary
ppermutes), sparse ring, q8 planar wire, and check its scopes:

* every instruction whose ``op_name`` comes from the step's traced code
  holds one ``round/<stage>`` scope;
* every instruction under ``round/mix`` holds exactly one wire stage (the
  fused round's deferred last local step holds ``sgd/grad`` or
  ``sgd/update`` there instead);
* ``round/metrics``, ``sgd/grad`` and ``sgd/update`` are there.

The compile runs in a subprocess (two host devices; the main test
process keeps seeing one) with the persistent compilation cache off: a
cached executable keeps the metadata of the program that compiled it.
"""
import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ROUND_STAGES = ("keys", "local_sgd", "mix", "metrics", "telemetry")
WIRE_STAGES = ("pack", "scales", "encode", "stream", "exchange", "decode",
               "unpack")
SGD_STAGES = ("grad", "update")

_ROUND = re.compile(r"(?:^|/)round/(%s)(?:/|$)" % "|".join(ROUND_STAGES))
_WIRE = re.compile(r"(?:^|/)wire/(%s)(?:/|$)" % "|".join(WIRE_STAGES))
# A scope opened right under ``vmap`` reads ``vmap(sgd/grad)/...``.
_SGD = re.compile(r"(?:^|/|\()sgd/(%s)(?:/|$|\))" % "|".join(SGD_STAGES))

_COMPILE = """
    import json, re, sys
    from repro.launch import train
    args = ["--arch", "olmo-1b", "--layers", "1", "--clients", "4",
            "--clients-per-shard", "2", "--local-steps", "2",
            "--batch", "1", "--seq", "16", "--bits", "8",
            "--mixer-impl", "sparse", "--wire", "planar", "--rounds", "1"]
    if FUSED:
        args.append("--fuse-round")
    res = train.main(args)
    txt = res.step.lower(res.state, res.batches).compile().as_text()
    # The stack-frame tables: frame id -> the file of its innermost frame.
    tabs, sec = {}, None
    for line in txt.splitlines():
        if line in ("FileNames", "FileLocations", "StackFrames"):
            sec = tabs.setdefault(line, {})
        elif re.match(r"^(%|ENTRY|FunctionNames)", line):
            sec = None
        elif sec is not None and re.match(r"^\\d+ ", line):
            i, v = line.split(" ", 1)
            sec[int(i)] = v
    def frame_file(fid):
        loc = re.search(r"file_location_id=(\\d+)", tabs["StackFrames"][fid])
        f = re.search(r"file_name_id=(\\d+)",
                      tabs["FileLocations"][int(loc.group(1))])
        return tabs["FileNames"][int(f.group(1))]
    ops = []
    for line in txt.splitlines():
        m = re.search(r'op_name="([^"]*)"(?: stack_frame_id=(\\d+))?', line)
        if m and " = " in line:
            fid = m.group(2)
            ops.append([" constant(" in line,
                        frame_file(int(fid)) if fid else None, m.group(1)])
    print("OPS " + json.dumps(ops))
"""


def _compiled_ops(fused: bool) -> list:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=2").strip()
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    code = f"FUSED = {fused}\n" + textwrap.dedent(_COMPILE)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=REPO)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    line, = [l for l in r.stdout.splitlines() if l.startswith("OPS ")]
    return json.loads(line[4:])


@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
def test_every_round_step_op_holds_one_stage(fused):
    ops = _compiled_ops(fused)
    # Traced code: an op_name with a source frame under the step's jit.
    # Parameters carry their argument path, reducer bodies a bare
    # primitive name; what XLA inserts carries no frame (its op_name, if
    # any, is the caller's path or its own instruction name). A constant
    # is a literal baked into the program, not an op the device runs;
    # JAX names one computed eagerly while tracing (the RoPE frequencies)
    # after its top-level place.
    traced = [(name, src) for const, src, name in ops
              if src and name.startswith("jit(round_step)/") and not const]
    assert len(traced) > 1000

    def staged(name):
        stages = {m.group(1) for m in _ROUND.finditer(name)}
        if len(stages) != 1:
            return False
        if stages != {"mix"}:
            return True
        wire = {m.group(1) for m in _WIRE.finditer(name)}
        sgd = {m.group(1) for m in _SGD.finditer(name)} if fused else set()
        return len(wire) + len(sgd) == 1

    bad = sorted({name for name, src in traced if not staged(name)})
    if fused:
        # The fused round takes two gradients outside the K-step scan.
        # Differentiating the attention's KV scan there, JAX hoists its
        # loop-invariant part (the RoPE table and the causal mask, which
        # read only constants) out of the scan with the name stack reset
        # (``lax`` scan partial evaluation), so those few model ops land
        # at the level the gradient was taken with no scope of the step.
        hoisted = {name for name, src in traced
                   if not staged(name) and "/repro/models/" in src}
        assert hoisted and len(hoisted) < 20, sorted(hoisted)
        bad = sorted(set(bad) - hoisted)
    assert bad == [], bad[:20]

    traced = [name for name, _ in traced]
    seen = {m.group(1) for n in traced for m in _ROUND.finditer(n)}
    assert {"keys", "local_sgd", "mix", "metrics"} <= seen
    mix = [n for n in traced if "/round/mix/" in n]
    assert {m.group(1) for n in mix for m in _WIRE.finditer(n)} \
        == set(WIRE_STAGES)
    assert any("/wire/encode/noise/" in n for n in mix)
    # At K=2 the fused round's head scan is empty: both updates it owns
    # run in the tail (the Pallas kernels fold them into the wire).
    sgd = [n for n in traced if "/round/local_sgd/" in n]
    assert {m.group(1) for n in sgd for m in _SGD.finditer(n)} \
        == ({"grad"} if fused else set(SGD_STAGES))
