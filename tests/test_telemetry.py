"""Telemetry subsystem: schema strictness, sink/tracer behaviour, the
with_telemetry off-path bitwise guarantee, and metric parity (consensus,
wire bits, exact quantizer replay) across the sync / async / pooled
execution paths."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AsyncConfig, ClientPool, DFedAvgMConfig, MixingSpec,
                        PoolSchedule, PooledRunner, QuantConfig, SpeedModel,
                        TopologySchedule, init_async_state, init_round_state,
                        make_async_engine, make_round_step, ring_graph)
from repro.core.mixing import _quant_leaf_keys
from repro.core.quantize import dequantize_int, message_bits, quantize_int
from repro.telemetry import (QUANT_SAMPLE_LANES, SCHEMA_VERSION, RunLog,
                             Telemetry, Tracer, quant_round_telemetry,
                             telemetry_host, validate_record)
from repro.telemetry.schema import require_valid

M, D = 8, 12


def quad_problem(seed=1):
    cs = jax.random.normal(jax.random.PRNGKey(seed), (M, D))

    def loss_fn(p, batch, rng):
        return 0.5 * jnp.sum((p["w"] - batch["c"]) ** 2)

    batches = {"c": jnp.broadcast_to(cs[:, None], (M, 4, D))}
    return cs, loss_fn, batches


def _params_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _run_pair(cfg, spec, rounds=20, token=None, key=2):
    """The same trajectory with telemetry off and on; returns both
    (state, metrics) pairs."""
    _, loss_fn, batches = quad_problem()
    out = []
    for wt in (False, True):
        step = jax.jit(make_round_step(loss_fn, cfg, spec,
                                       with_telemetry=wt))
        st = init_round_state({"w": jnp.zeros((M, D))},
                              jax.random.PRNGKey(key), token=token)
        for _ in range(rounds):
            st, mt = step(st, batches)
        out.append((st, mt))
    return out


# -- schema ---------------------------------------------------------------

def test_schema_valid_round_record():
    rec = {"kind": "round", "t": 3, "loss": 0.5, "wall_s": 1.25,
           "consensus_dist": 0.1, "staleness_hist": [1, 2]}
    assert validate_record(rec) == []
    require_valid(rec)  # must not raise


def test_schema_rejects_malformed():
    assert validate_record({"kind": "nope"})          # unknown kind
    assert validate_record({"kind": "round", "t": 0})  # missing required
    assert validate_record({"kind": "round", "t": 0, "loss": 0.1,
                            "wall_s": 0.0, "typo_metric": 1.0})
    assert validate_record({"kind": "round", "t": "0", "loss": 0.1,
                            "wall_s": 0.0})            # wrong type
    assert validate_record({"kind": "round", "t": True, "loss": 0.1,
                            "wall_s": 0.0})            # bool is not int
    with pytest.raises(ValueError):
        require_valid({"kind": "info"})


# -- sink -----------------------------------------------------------------

def test_runlog_jsonl_roundtrip(tmp_path):
    path = tmp_path / "run.jsonl"
    log = RunLog(jsonl=str(path))
    log.start(config={"rounds": 2})
    log.info("topology: ring(8)")
    log.round(0, 1.5, consensus_dist=0.2, quant_err_sq=None)  # None dropped
    log.round(1, 1.2, console=False)
    log.end(2, final_loss=1.2)
    log.close()

    recs = [json.loads(l) for l in path.read_text().splitlines()]
    assert [r["kind"] for r in recs] == \
        ["run_start", "info", "round", "round", "run_end"]
    assert recs[0]["schema"] == SCHEMA_VERSION
    assert "quant_err_sq" not in recs[2]
    for r in recs:
        assert validate_record(r) == [], r
    assert all("wall_s" in r for r in recs if r["kind"] == "round")


def test_runlog_rejects_unknown_field(tmp_path):
    log = RunLog(jsonl=str(tmp_path / "bad.jsonl"))
    log.start(config={})
    with pytest.raises(ValueError):
        log.round(0, 1.0, not_a_metric=3.0)
    log.close()


# -- tracer ---------------------------------------------------------------

def test_tracer_chrome_events(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("round/step", t=0):
        pass
    with tr.span("round/step", t=1):
        pass
    with tr.span("round/d2h"):
        pass
    trace = tr.to_chrome_trace()
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    ms = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    assert len(xs) == 3 and ms, "complete events + thread metadata"
    for e in xs:
        assert e["dur"] >= 0 and e["ts"] >= 0
    assert xs[0]["args"] == {"t": 0}
    assert {e["name"] for e in xs} == {"round/step", "round/d2h"}
    p = tmp_path / "trace.json"
    tr.save(p)
    assert json.loads(p.read_text())["traceEvents"]


def test_tracer_disabled_is_silent():
    tr = Tracer(enabled=False)
    with tr.span("round/step"):
        pass
    assert tr.events == []
    assert tr.to_chrome_trace()["traceEvents"] == []


# -- off-path bitwise guarantee -------------------------------------------

@pytest.mark.parametrize("quant", [None, QuantConfig(bits=8)])
def test_with_telemetry_off_path_bitwise_static(quant):
    cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4, quant=quant)
    (st_off, mt_off), (st_on, mt_on) = _run_pair(cfg, MixingSpec.ring(M))
    assert _params_equal(st_off.params, st_on.params)
    assert "telemetry" not in mt_off
    assert isinstance(mt_on["telemetry"], Telemetry)


def test_with_telemetry_off_path_bitwise_scheduled():
    sched = TopologySchedule.edge_sample(ring_graph(M), p_edge=0.5)
    cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=2,
                         quant=QuantConfig(bits=8))
    (st_off, _), (st_on, mt_on) = _run_pair(cfg, sched)
    assert _params_equal(st_off.params, st_on.params)
    tel = mt_on["telemetry"]
    assert float(tel.quant_err_sq) <= float(tel.quant_bound) + 1e-12


# -- metric parity --------------------------------------------------------

def test_telemetry_consensus_matches_metrics():
    cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4)
    _, (st, mt) = _run_pair(cfg, MixingSpec.ring(M), rounds=5)
    tel = mt["telemetry"]
    assert np.array_equal(np.asarray(tel.consensus_dist),
                          np.asarray(mt["consensus_dist"]))
    assert np.array_equal(np.asarray(tel.local_drift),
                          np.asarray(mt["local_drift"]))


def test_telemetry_wire_bits_static_ring():
    """Static dense ring: every directed edge fires every round, so the
    realized wire equals the deterministic per-round bill."""
    q = QuantConfig(bits=8)
    cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=2, quant=q)
    _, (st, mt) = _run_pair(cfg, MixingSpec.ring(M), rounds=3)
    tel = mt["telemetry"]
    edges = ring_graph(M).num_directed_edges()
    assert float(tel.live_edges) == float(edges)
    assert float(tel.wire_bits) == float(message_bits(D, q) * edges)


def test_quant_replay_exact_and_sampled():
    """Full replay reproduces the per-lane codec exactly; a strided
    lane sample is the mean of those exact per-lane values over
    lanes ``range(0, m, m // s)``."""
    q = QuantConfig(bits=8)
    key = jax.random.PRNGKey(3)
    kx, kz, kq = jax.random.split(key, 3)
    x = {"w": jax.random.normal(kx, (M, D))}
    z = {"w": jnp.asarray(x["w"]) + 0.01 * jax.random.normal(kz, (M, D))}

    leaf_keys = _quant_leaf_keys(kq, 1, M)
    err_lane, bound_lane = [], []
    for i in range(M):
        drow = (z["w"][i] - x["w"][i]).astype(jnp.float32)
        code, s = quantize_int(drow, q, leaf_keys[0][i])
        err_lane.append(float(jnp.sum((dequantize_int(code, s) - drow) ** 2)))
        bound_lane.append(D / 4.0 * float(s) ** 2)

    qe, qb, qs = quant_round_telemetry(x, z, q, kq)
    np.testing.assert_allclose(float(qe), np.mean(err_lane), rtol=1e-6)
    np.testing.assert_allclose(float(qb), np.mean(bound_lane), rtol=1e-6)
    assert float(qe) <= float(qb)

    s_lanes = 2
    ids = list(range(0, M, M // s_lanes))[:s_lanes]
    qe_s, qb_s, _ = quant_round_telemetry(x, z, q, kq,
                                          sample_lanes=s_lanes)
    np.testing.assert_allclose(
        float(qe_s), np.mean([err_lane[i] for i in ids]), rtol=1e-6)
    np.testing.assert_allclose(
        float(qb_s), np.mean([bound_lane[i] for i in ids]), rtol=1e-6)


def test_quant_replay_lane_weight_excludes_gated():
    """A gated (zero-delta) lane trips the codec's s=1 zero-amax guard;
    lane_weight must keep it out of the averages."""
    q = QuantConfig(bits=8)
    key = jax.random.PRNGKey(4)
    x = {"w": jax.random.normal(key, (M, D))}
    z = jax.tree.map(jnp.copy, x)                      # all deltas zero
    active = jnp.zeros((M,)).at[0].set(1.0)
    zw = {"w": z["w"].at[0].add(0.01)}
    _, qb_all, _ = quant_round_telemetry(x, zw, q, key)
    _, qb_act, _ = quant_round_telemetry(x, zw, q, key, lane_weight=active)
    # 7 zero-delta lanes each contribute D/4 * 1.0 to the unweighted mean
    assert float(qb_all) > 0.1
    assert float(qb_act) < 1e-4


# -- async path -----------------------------------------------------------

def test_async_telemetry_histogram_and_bound():
    _, loss_fn, batches = quad_problem()
    speed = SpeedModel.straggler(mean=1.0, sigma=0.5, frac=1.0 / M,
                                 factor=10.0)
    acfg = AsyncConfig(speed=speed, max_staleness=4)
    sched = TopologySchedule.edge_sample(ring_graph(M), p_edge=0.5)
    cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=2,
                         quant=QuantConfig(bits=8))
    evs = jax.tree.map(lambda l: jnp.broadcast_to(l[None], (M,) + l.shape),
                       batches)
    stacked = {"w": jnp.zeros((M, D))}
    params = {}
    for wt in (False, True):
        eng = jax.jit(make_async_engine(loss_fn, cfg, sched, acfg,
                                        with_telemetry=wt))
        ast = init_async_state(stacked, jax.random.PRNGKey(5), speed)
        for _ in range(2):
            ast, amt = eng(ast, evs)
        params[wt] = jax.device_get(ast.params)
    assert _params_equal(params[False], params[True])
    tel = amt["telemetry"]
    hist = np.asarray(tel.staleness_hist)              # [events, buckets]
    assert hist.shape[1] == acfg.max_staleness + 2
    assert (hist.sum(axis=1) == M).all()
    qe, qb = np.asarray(tel.quant_err_sq), np.asarray(tel.quant_bound)
    assert (qe <= qb + 1e-12).all()
    assert (np.asarray(tel.dropped_edges) >= 0).all()


# -- pooled path ----------------------------------------------------------

def _pool_problem():
    template = {"w": jnp.zeros((6, 4), jnp.float32),
                "b": jnp.zeros((4,), jnp.float32)}

    def loss_fn(p, b, r):
        return jnp.mean((b["x"] @ p["w"] + p["b"] - b["y"]) ** 2)

    def bf(idx, t):
        ks = jax.vmap(lambda c: jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(5), c), t))(
                jnp.asarray(idx, jnp.int32))

        def one(k):
            kx, ky = jax.random.split(k)
            return {"x": jax.random.normal(kx, (2, 4, 6)),
                    "y": jax.random.normal(ky, (2, 4, 4))}

        return jax.vmap(one)(ks)

    return template, loss_fn, bf


def test_pooled_telemetry_fields_and_bitwise():
    template, loss_fn, bf = _pool_problem()
    m, k = 32, 8
    cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=2,
                         quant=QuantConfig(bits=8))
    stores = {}
    for wt in (False, True):
        runner = PooledRunner(ClientPool(template, m),
                              PoolSchedule.ring_partial(m, k / m), loss_fn,
                              cfg, bf, key=jax.random.PRNGKey(1),
                              telemetry=wt)
        for _ in range(3):
            mt = runner.round()
        stores[wt] = runner.pool.fetch(np.arange(m))
    assert _params_equal(stores[False], stores[True])
    assert mt["cohort_size"] == k
    assert mt["quant_err_sq"] <= mt["quant_bound"] + 1e-12
    # A scattered cohort may draw zero adjacent ring pairs, so live_edges
    # can legitimately be 0 — the invariant is the realized-bill relation.
    d_client = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(template))
    assert mt["wire_bits"] == message_bits(d_client, cfg.quant) * \
        mt["live_edges"]


# -- host conversion ------------------------------------------------------

def test_telemetry_host_drops_none_and_converts():
    tel = Telemetry(consensus_dist=jnp.float32(0.25),
                    staleness_hist=jnp.asarray([3, 4, 1], jnp.int32))
    out = telemetry_host(tel)
    assert out == {"consensus_dist": 0.25, "staleness_hist": [3, 4, 1]}
    assert isinstance(out["consensus_dist"], float)
    assert all(isinstance(c, int) for c in out["staleness_hist"])


# -- benchmark timing primitive -------------------------------------------

def test_timeit_best_call_index_and_carry():
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from benchmarks.common import timeit_best

    seen = []

    def body(i, carry):
        seen.append(i)
        return carry + i

    best, carry = timeit_best(body, 0, iters=2, reps=3, warmup=2)
    assert seen == list(range(8)), "global call index stays monotone"
    assert carry == sum(range(8)), "carry threads through warmup + reps"
    assert best >= 0.0
