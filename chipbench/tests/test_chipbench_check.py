"""The check that decides ``correct``, driven through the harness at a
size a test can hold, on the CPU (the look for a chip is skipped): a
sound run passes every cell's limits; the timed path broken underneath
(state returned unchanged, half of each batch left out, the exchange
left out) and the control (the reference with float8 matmuls in the
program's place) each fail them."""
import time

import jax
import numpy as np
import pytest

import check
import harness
import spec
import traffic

TINY = {"paths": ["chipbench/tests/data"],
        "configs": [{"name": "tiny-olmo",
                     "file": "chipbench/tests/data/tiny-olmo.json"}],
        "workloads": [{"name": "tiny", "config": "tiny-olmo",
                       "traffic": "tiny", "chips": 1}],
        "end_to_end": [{"name": "tokens_per_s_per_chip",
                        "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}
BENCH = spec.load()
CELL_LIMITS = {w["name"]: spec.Cell(BENCH, w["name"]).limits
               for w in BENCH["workloads"]}
SEED = 2**31 + 12345


@pytest.fixture(scope="module")
def no_compile_cache():
    keys = ("jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_compilation_cache_dir")
    saved = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


@pytest.fixture(scope="module")
def tiny(no_compile_cache):
    return spec.Cell(TINY, "tiny")


@pytest.fixture(scope="module")
def runs(tiny):
    """The harness's checks of one sound run and of each planted fault."""
    out = {}
    for fault in (None,) + harness.FAULTS:
        t0 = time.perf_counter()
        res = harness.run(tiny, SEED, 0.5, False,
                          process_age=lambda: time.perf_counter() - t0,
                          require_chip=False, fault=fault)
        out[fault] = {k: v["value"] for k, v in res["checks"].items()}
    return out


@pytest.fixture(scope="module")
def control(tiny):
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
    return check.numbers(low, base)


@pytest.mark.parametrize("cell", sorted(CELL_LIMITS))
def test_sound_run_is_correct(runs, cell):
    ok, checks = check.verdict(runs[None], CELL_LIMITS[cell])
    assert ok, checks


@pytest.mark.parametrize("fault", harness.FAULTS)
@pytest.mark.parametrize("cell", sorted(CELL_LIMITS))
def test_planted_fault_is_not_correct(runs, fault, cell):
    ok, checks = check.verdict(runs[fault], CELL_LIMITS[cell])
    assert not ok, checks


@pytest.mark.parametrize("cell", sorted(CELL_LIMITS))
def test_control_is_not_correct(control, cell):
    ok, checks = check.verdict(control, CELL_LIMITS[cell])
    assert not ok, checks


def test_unchanged_state_reads_one():
    r = {"losses": np.zeros(3), "n1": np.ones((2, 3)),
         "n3": np.ones((2, 3)), "spread": np.zeros(3),
         "g0": np.ones(3)}
    p = dict(r, n1=np.zeros((2, 3)), n3=np.zeros((2, 3)))
    assert check.numbers(p, r)["change1"] == 1.0


def test_leaves_without_gradient_do_not_count():
    r = {"losses": np.zeros(3), "n1": np.ones((1, 3)),
         "n3": np.ones((1, 3)), "spread": np.zeros(3),
         "g0": np.array([1.0, 1.0, 1e-9])}
    p = dict(r, n1=np.array([[1.0, 1.0, 5.0]]))
    assert check.numbers(p, r)["change1"] == 0.0


def test_ring_matrix_is_doubly_stochastic():
    for m in (2, 4, 5):
        W = check.ring_matrix(m, 0.5)
        assert np.allclose(W.sum(0), 1) and np.allclose(W.sum(1), 1)
    assert np.allclose(check.ring_matrix(2, 0.5), 0.5)


def test_unknown_topology_is_refused():
    with pytest.raises(SystemExit, match="topology"):
        check.mixing_matrix({"topology": "torus", "clients": 4,
                             "self_weight": 0.5})

