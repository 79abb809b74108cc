"""The readers of the round step's stage scopes (CPU): on a reduced trace
built by hand, where every answer is known, and on the trace recorded
on a TPU v5e before the program had these scopes."""
import json
import os

import pytest

import spec
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1000   # ns

SGD = "jit(f)/round/local_sgd/vmap()/while"
MIX = "jit(f)/round/mix/shard_map"
# (chip, name, scope, start_us, end_us)
OPS = [
    (0, "while.1", SGD, 0, 100),
    (0, "fusion.1", SGD + "/body/closed_call/sgd/grad/jvp()/dot", 0, 60),
    (0, "fusion.2", SGD + "/body/closed_call/sgd/update/mul", 60, 80),
    (0, "copy.1", "", 80, 90),   # inside the loop: covered by it
    (0, "all-reduce.1", "jit(f)/round/metrics/reduce_sum", 100, 110),
    (0, "fusion.3", MIX + "/wire/pack/concatenate", 110, 120),
    (0, "fusion.4", MIX + "/wire/scales/reduce_max", 120, 125),
    (0, "fusion.5", MIX + "/wire/encode/noise/threefry2x32", 125, 135),
    (0, "quantize_pack_buffer", MIX + "/wire/encode/pallas_call", 130, 145),
    (0, "fusion.6", MIX + "/wire/stream/concatenate", 145, 148),
    (0, "collective-permute", MIX + "/wire/exchange/ppermute", 148, 160),
    (0, "dequant_mix_buffer", MIX + "/wire/decode/pallas_call", 160, 170),
    (0, "fusion.7", MIX + "/wire/unpack/concatenate", 170, 178),
    (0, "fusion.8", "jit(f)/reduce_sum", 180, 190),
    (0, "copy.2", "", 185, 195),
    (0, "copy.3", "", 195, 205),
    (0, "fusion.9", "jit(f)/round/keys/threefry2x32", 200, 210),
    (1, "fusion.1", "jit(f)/round/local_sgd/sgd/update/add", 0, 30),
    (1, "all-reduce.1", "jit(f)/round/metrics/reduce_sum", 30, 60),
    (1, "fusion.3", MIX + "/wire/unpack/slice", 60, 64),
    (1, "copy.1", "", 64, 70),
]


@pytest.fixture(scope="module")
def red():
    chips = [[tr.Op(s * US, e * US, n, sc) for c, n, sc, s, e in OPS
              if c == chip] for chip in (0, 1)]
    return tr.Reduced(chips=chips, spans=[("bench/wait", 0, 220 * US)],
                      lo=0, hi=220 * US)


@pytest.fixture(scope="module")
def read():
    cell = spec.Cell(spec.load(), "olmo1b.k8.q8")
    return lambda name, trace, rounds=2: cell.reader(name)(
        trace, {"rounds": rounds})


def test_noise_counts_under_the_encoder_and_its_own_reader(red, read):
    # chip 0: the noise draw 125-135 and the kernel 130-145 overlap.
    assert red.scope_s("wire/encode") == pytest.approx([20e-6, 0.0])
    assert red.scope_s("wire/encode/noise") == pytest.approx([10e-6, 0.0])
    assert read("wire_noise_ms", red) == pytest.approx(10e-3 / 2 / 2)


def test_stage_readers_on_the_hand_made_trace(red, read):
    # Mean over the two chips, then per round (2 rounds), in ms.
    assert read("step_metrics_ms", red) == pytest.approx(
        (10 + 30) / 2 / 2 * 1e-3)
    # pack 10 + scales 5 + stream 3 + unpack 8 on chip 0; unpack 4 on 1.
    assert read("wire_buffer_ms", red) == pytest.approx(
        (26 + 4) / 2 / 2 * 1e-3)
    assert read("sgd_update_ms", red) == pytest.approx(
        (20 + 30) / 2 / 2 * 1e-3)
    # chip 0: 180-195 and 195-200 (the rest of copy.3 runs beside a
    # round/keys op); copy.1 lies inside the loop. chip 1: 64-70.
    assert read("unscoped_ms", red) == pytest.approx(
        (20 + 6) / 2 / 2 * 1e-3)


def test_stage_readers_find_nothing_without_their_scopes(read):
    ops = [[tr.Op(0, 10 * US, "fusion.1", "jit(f)/round/local_sgd/dot")]]
    red = tr.Reduced(chips=ops, spans=[("bench/wait", 0, 20 * US)], lo=0,
                     hi=20 * US)
    for name in ("step_metrics_ms", "wire_buffer_ms", "wire_noise_ms",
                 "sgd_update_ms"):
        assert read(name, red, rounds=1) is None
    assert read("unscoped_ms", red) == 0.0


def test_unscoped_time_of_the_recorded_trace(read):
    """The recorded K=1 round (PR 12's program) has only the
    ``round/local_sgd`` and ``round/mix`` scopes: its metrics, keys and
    the rest read as unscoped, and the new stage readers find nothing."""
    with open(os.path.join(DATA, "olmo1b_k1_round.scopes.json")) as f:
        scopes = json.load(f)
    red = tr.reduce_file(os.path.join(DATA, "olmo1b_k1_round.xplane.pb"),
                         n_chips=1, scopes=scopes)
    free = read("unscoped_ms", red, rounds=1)
    sgd, = red.scope_s("round/local_sgd")
    mix, = red.scope_s("round/mix")
    # The two scopes never overlap: what is left of the busy time is free.
    assert free * 1e-3 == pytest.approx(red.busy_s - sgd - mix, rel=1e-6)
    assert free > 100
    for name in ("step_metrics_ms", "wire_buffer_ms", "wire_noise_ms",
                 "sgd_update_ms"):
        assert read(name, red, rounds=1) is None
