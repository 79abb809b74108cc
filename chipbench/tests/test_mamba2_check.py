"""The check that decides ``correct``, for Mamba2, driven through the
harness on the CPU at a size a test can hold (the look for a chip is
skipped): a sound run of a tiny Mamba2 passes the limits of the
``mamba2.k4.q8`` cell; the timed path broken underneath (state returned
unchanged, half of each batch left out, the exchange left out) and the
control (the reference with float8 matmuls in the program's place) each
fail them. The sequence, 160, runs one full SSD chunk of 128 and a
ragged one."""
import time

import jax
import pytest

import check
import harness
import spec
import traffic

TINY = {"paths": ["chipbench/tests/data"],
        "configs": [{"name": "tiny-mamba2",
                     "file": "chipbench/tests/data/tiny-mamba2.json"}],
        "workloads": [{"name": "tiny-mamba2", "config": "tiny-mamba2",
                       "traffic": "tiny-mamba2", "chips": 1}],
        "end_to_end": [{"name": "tokens_per_s_per_chip",
                        "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}
LIMITS = spec.Cell(spec.load(), "mamba2.k4.q8").limits
SEED = 2**31 + 54321


@pytest.fixture(scope="module")
def tiny():
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield spec.Cell(TINY, "tiny-mamba2")
    jax.config.update("jax_enable_compilation_cache", saved)


@pytest.fixture(scope="module")
def runs(tiny):
    """The harness's numbers of one sound run and of each planted
    fault."""
    out = {}
    for fault in (None,) + harness.FAULTS:
        t0 = time.perf_counter()
        res = harness.run(tiny, SEED, 0.5, False,
                          process_age=lambda: time.perf_counter() - t0,
                          require_chip=False, fault=fault)
        out[fault] = ({k: v["value"] for k, v in res["checks"].items()},
                      res["failed"])
    return out


def test_sound_run_is_correct(runs):
    nums, failed = runs[None]
    ok, checks = check.verdict(nums, LIMITS)
    assert ok and failed == 0, checks


@pytest.mark.parametrize("fault", harness.FAULTS)
def test_planted_fault_is_not_correct(runs, fault):
    ok, checks = check.verdict(runs[fault][0], LIMITS)
    assert not ok, checks


def test_control_is_not_correct(tiny):
    t, ref = tiny.traffic, tiny.reference()
    key = traffic.seed_key(SEED)
    k_w, k_data, _, k_q, k_other = (jax.random.fold_in(key, i)
                                    for i in range(5))

    def feed(r):
        return traffic.round_batches(k_data, r, m=t["clients"],
                                     K=t["local_steps"], batch=t["batch"],
                                     seq=t["seq"],
                                     vocab=tiny.model["vocab_size"])

    base = check.reference_readings(ref, tiny.model, t, k_w, feed,
                                    q_key=k_q)
    low = check.reference_readings(ref, tiny.model, t, k_w, feed,
                                   q_key=k_other, fp8=True)
    ok, checks = check.verdict(check.numbers(low, base), LIMITS)
    assert not ok, checks
