"""The reduction from a profiler trace to per-layer numbers (CPU), on a
trace built by hand where every answer is known, and on a trace
recorded on a TPU v5e and cut to a few rounds."""
import json
import os

import pytest
from jax.profiler import ProfileData

import spec
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# (chip, name, scope, start_us, end_us)
DEVICE = [
    (0, "fusion.1", "jit(f)/round/local_sgd/dot", 0, 40),
    (0, "fusion.2", "jit(f)/round/local_sgd/transpose(jvp(x))/dot", 40, 60),
    (0, "quantize_pack_buffer", "jit(f)/round/mix/wire/encode/pallas", 70, 80),
    (0, "collective-permute-done", "jit(f)/round/mix/ppermute", 80, 90),
    (0, "dequant_mix_buffer", "jit(f)/round/mix/wire/decode/pallas", 85, 95),
    (1, "fusion.1", "jit(f)/round/local_sgd/dot", 0, 50),
    (1, "collective-permute-done", "jit(f)/round/mix/ppermute", 50, 80),
]
# (name, start_us, end_us)
HOST = [("bench/data", 0, 5), ("bench/dispatch", 5, 10),
        ("bench/wait", 60, 100)]


def _proto(device, host) -> str:
    names = sorted({d[1] for d in device} | {h[0] for h in host})
    meta = {n: i + 1 for i, n in enumerate(names)}
    md = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                 f'"{n}" }} }}\n' for n, i in meta.items())
    planes = []
    for chip in sorted({d[0] for d in device}):
        evs = "".join(
            f"events {{ metadata_id: {meta[n]} offset_ps: {s * 10**6} "
            f"duration_ps: {(e - s) * 10**6} stats {{ metadata_id: 1 "
            f'str_value: "{sc}" }} }}\n'
            for c, n, sc, s, e in device if c == chip)
        planes.append(
            f'planes {{ id: {chip + 1} name: "/device:TPU:{chip}"\n'
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0\n{evs}}}\n'
            f'{md}stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} '
            f'}}\n}}\n')
    evs = "".join(f"events {{ metadata_id: {meta[n]} offset_ps: "
                  f"{s * 10**6} duration_ps: {(e - s) * 10**6} }}\n"
                  for n, s, e in host)
    planes.append(f'planes {{ id: 9 name: "/host:CPU"\n'
                  f'lines {{ id: 1 name: "main" timestamp_ns: 0\n{evs}}}\n'
                  f"{md}}}\n")
    return "".join(planes)


@pytest.fixture(scope="module")
def red():
    return tr.reduce_profile(ProfileData.from_text_proto(
        _proto(DEVICE, HOST)), n_chips=2)


def test_window_and_busy(red):
    assert red.window_s == pytest.approx(100e-6)
    # chip 0 busy 0-60, 70-95 = 85 us; chip 1 busy 0-80.
    assert red.busy_s == pytest.approx((85e-6 + 80e-6) / 2)


def test_scope_time_counts_nested_scopes(red):
    assert red.scope_s("round/local_sgd") == pytest.approx([60e-6, 50e-6])
    assert red.scope_s("round/mix") == pytest.approx([25e-6, 30e-6])
    assert red.scope_s("wire/encode", "wire/decode") == \
        pytest.approx([20e-6, 0.0])
    assert red.scope_s("round/loc") == [0.0, 0.0]


def test_collective_exposed(red):
    # chip 0: permute 80-90, decode covers 85-90 -> 5 us; chip 1: 30 us.
    assert red.collective_exposed_s() == pytest.approx([5e-6, 30e-6])


def test_breakdown_ops_and_gaps(red):
    b = red.breakdown()
    assert b["device_ops"][0] == ["fusion.1 @ jit(f)/round/local_sgd/dot",
                                  pytest.approx(40e-6)]
    # chip 0 idles 60-70 (host waiting) and 95-100.
    assert b["idle_gaps"] == [["bench/wait", pytest.approx(10e-6)],
                              ["bench/wait", pytest.approx(5e-6)]]


def test_no_collective_reads_nothing():
    dev = [d for d in DEVICE if "permute" not in d[1]]
    red = tr.reduce_profile(ProfileData.from_text_proto(
        _proto(dev, HOST)), n_chips=2)
    assert red.collective_exposed_s() is None


def test_interval_helpers():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_readers_on_the_hand_made_trace(red):
    c = spec.Cell(spec.load(), "olmo1b.k8.q8")
    ctx = {"rounds": 2, "tokens": 1000, "chips": 2, "model": c.model,
           "traffic": c.traffic, "kind": "TPU v5 lite"}
    assert c.reader("device_idle_frac")(red, ctx) == \
        pytest.approx(1 - 82.5 / 100)
    assert c.reader("local_sgd_ms")(red, ctx) == pytest.approx(55e-3 / 2)
    assert c.reader("mix_ms")(red, ctx) == pytest.approx(27.5e-3 / 2)
    assert c.reader("collective_exposed_ms")(red, ctx) == \
        pytest.approx(30e-3 / 2)
    assert c.reader("codec_roofline")(red, ctx) > 0
    with pytest.raises(KeyError):
        c.reader("mfu")(red, dict(ctx, kind="no such chip"))


def test_recorded_trace():
    """One K=1 round of ``olmo1b.k1.q8`` recorded on a TPU v5e (the
    program's own step, PR 12), cut to the last round's window; event
    names cut to the HLO instruction, scopes from the compiled HLO."""
    with open(os.path.join(DATA, "olmo1b_k1_round.scopes.json")) as f:
        scopes = json.load(f)
    red = tr.reduce_file(os.path.join(DATA, "olmo1b_k1_round.xplane.pb"),
                         n_chips=1, scopes=scopes)
    assert red.window_s == pytest.approx(0.985002767, rel=1e-6)
    assert red.busy_s == pytest.approx(0.982279886, rel=1e-6)
    sgd, = red.scope_s("round/local_sgd")
    mix, = red.scope_s("round/mix")
    codec, = red.scope_s("wire/encode", "wire/decode")
    assert sgd == pytest.approx(0.215856735, rel=1e-6)
    assert mix == pytest.approx(0.641418994, rel=1e-6)
    assert codec == pytest.approx(0.41070979, rel=1e-6)
    assert sgd + mix <= red.busy_s
    assert red.collective_exposed_s() is None
    b = red.breakdown()
    assert b["device_ops"][0][0].startswith("dequant_mix_buffer.5 @ ")
    assert b["idle_gaps"][0] == ["bench/wait", pytest.approx(2.667137e-3)]
    selfs = sum(ns for _, ns in red.self_ns(0))
    assert selfs * 1e-9 == pytest.approx(red.busy_s, rel=1e-3)


def test_scope_from_hlo_metadata_when_stats_lack_it():
    hlo = ('  %fusion.1 = f32[8]{0} fusion(f32[8] %p), kind=kLoop, '
           'metadata={op_name="jit(f)/round/local_sgd/dot" '
           'source_file="x.py"}\n'
           '  ROOT %copy.2 = f32[8]{0} copy(%fusion.1), '
           'metadata={op_name="jit(f)/round/mix/copy"}\n')
    scopes = tr.hlo_scopes(hlo)
    assert scopes == {"fusion.1": "jit(f)/round/local_sgd/dot",
                      "copy.2": "jit(f)/round/mix/copy"}
    dev = [(0, "fusion.1", "", 0, 10), (0, "copy.2", "", 10, 15)]
    red = tr.reduce_profile(ProfileData.from_text_proto(
        _proto(dev, HOST)), n_chips=1, scopes=scopes)
    assert red.scope_s("round/local_sgd") == pytest.approx([10e-6])
    assert red.scope_s("round/mix") == pytest.approx([5e-6])
