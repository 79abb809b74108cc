"""Async gossip engine + event clock + satellite invariants.

The load-bearing guarantees, pinned hypothesis-free (the property sweep
over random staleness patterns rides along at the bottom, guarded):

  * ZERO-DELAY EQUIVALENCE — under a constant speed model every client
    finishes every event simultaneously, and the async engine reproduces
    synchronous ``make_round_step`` BIT FOR BIT (fp32 and stochastic-q8,
    static specs and schedules). The sparse-backend half of this claim
    runs on a real 8-device mesh in test_sparse_backend_mesh.py.
  * staleness-reweighted event matrices stay row-stochastic with the
    removed mass folded into the self weight; busy rows are e_i.
  * the ``lax.scan`` engine is bit-identical to per-event stepping.
  * compute-skip: schedules with a static active count gather/scatter the
    active lanes — same numerics, fewer FLOPs (asserted via
    ``launch.hlo_stats.traced_flops``).
  * the stateful random-walk token is in-graph RoundState and walks the
    base graph's edges.
  * cycle schedules compile per-member plans whose realized wire is
    member-sized, not union-sized.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AsyncConfig, DFedAvgMConfig, MixingSpec, QuantConfig,
                        SpeedModel, TopologySchedule, async_event_bits,
                        init_async_state, init_round_state, make_async_engine,
                        make_round_step, next_event, plan_round_bits,
                        staleness_eta, staleness_weights)
from repro.core.comm_cost import CommLedger
from repro.core.topology import Graph, ring_graph

M, D = 8, 12


def quad_problem(seed=1):
    cs = jax.random.normal(jax.random.PRNGKey(seed), (M, D))
    loss_fn = lambda p, b, r: 0.5 * jnp.sum((p["w"] - b["c"]) ** 2)
    batches = {"c": jnp.broadcast_to(cs[:, None], (M, 4, D))}
    return cs, loss_fn, batches


def dot_problem(seed=0):
    """A loss with real dot_generals so FLOP accounting has signal."""
    H = 32
    key = jax.random.PRNGKey(seed)
    params = {"w1": jax.random.normal(key, (M, D, H)) * 0.1,
              "w2": jax.random.normal(key, (M, H)) * 0.1}
    batches = {"x": jax.random.normal(key, (M, 4, 8, D)),
               "y": jax.random.normal(key, (M, 4, 8))}
    loss_fn = lambda p, b, r: jnp.mean(
        (jnp.tanh(b["x"] @ p["w1"]) @ p["w2"] - b["y"]) ** 2)
    return params, loss_fn, batches


def chain_from_order(order):
    adj = np.zeros((M, M), bool)
    for a, b in zip(order[:-1], order[1:]):
        adj[a, b] = adj[b, a] = True
    return Graph(adj, name="chain-perm")


# ---------------------------------------------------------------------------
# Event clock
# ---------------------------------------------------------------------------

def test_constant_speed_all_clients_tie_every_event():
    speed = SpeedModel.constant(mean=2.0)
    nr = speed.draw(jax.random.PRNGKey(0), M)
    t, ready = next_event(nr)
    assert float(t) == 2.0
    assert np.asarray(ready).sum() == M


def test_straggler_multipliers_and_draw():
    speed = SpeedModel.straggler(mean=1.0, sigma=0.3, frac=0.25, factor=8.0)
    mult = speed.multipliers(M)
    assert (mult[: speed.n_stragglers(M)] == 8.0).all()
    assert (mult[speed.n_stragglers(M):] == 1.0).all()
    dur = np.asarray(speed.draw(jax.random.PRNGKey(0), M))
    assert dur[:2].min() > dur[2:].max()   # 8x tail dominates the jitter
    t, ready = next_event(jnp.asarray(dur))
    assert np.asarray(ready).sum() == 1    # continuous times: unique argmin


def test_lognormal_is_mean_preserving():
    speed = SpeedModel.lognormal(mean=3.0, sigma=0.5)
    dur = np.asarray(speed.draw(jax.random.PRNGKey(0), 4096))
    assert abs(dur.mean() - 3.0) < 0.15


def test_speed_model_validation():
    with pytest.raises(ValueError):
        SpeedModel(kind="warp")
    with pytest.raises(ValueError):
        SpeedModel.constant(mean=0.0)
    with pytest.raises(ValueError):
        SpeedModel.straggler(factor=0.5)
    with pytest.raises(ValueError):
        AsyncConfig(discount="linear")
    with pytest.raises(ValueError):
        AsyncConfig(max_staleness=-1)


# ---------------------------------------------------------------------------
# Staleness-aware mixing weights
# ---------------------------------------------------------------------------

def _check_event_matrix(We, W, ready, m=M):
    assert np.allclose(We.sum(axis=1), 1.0, atol=1e-6)
    assert (We >= -1e-7).all()
    off = ~np.eye(m, dtype=bool)
    assert not np.any((We != 0) & off & (np.asarray(W) == 0)), \
        "staleness reweighting created weight outside W's support"
    for i in np.nonzero(np.asarray(ready) == 0)[0]:
        np.testing.assert_array_equal(We[i], np.eye(m)[i])


@pytest.mark.parametrize("discount", ["inverse", "power"])
def test_staleness_weights_rows_stochastic(discount):
    cfg = AsyncConfig(max_staleness=4, discount=discount, gamma=0.6)
    W = np.asarray(MixingSpec.ring(M, self_weight=0.5).W, np.float32)
    version = jnp.asarray([9, 3, 0, 2, 9, 1, 4, 4], jnp.int32)
    ready = jnp.asarray([1, 0, 1, 1, 0, 1, 1, 1], jnp.float32)
    We = np.asarray(staleness_weights(W, version, ready, cfg))
    _check_event_matrix(We, W, ready)
    # hard cutoff: client 0 (v=9) vs client 1 (v=3) lags 6 > 4 -> weight 0
    assert We[0, 1] == 0.0
    # a neighbor that LEADS (row 2, v=0 reads client 3, v=2) is not stale
    # from this row's perspective: EXACT base weight (rho(0) == 1)
    assert We[2, 3] == W[2, 3]


def test_staler_neighbors_get_smaller_weights():
    cfg = AsyncConfig(max_staleness=10, discount="inverse")
    W = np.asarray(MixingSpec.ring(M, self_weight=0.5).W, np.float32)
    ready = jnp.ones((M,), jnp.float32)
    v = jnp.zeros((M,), jnp.int32).at[0].set(6)
    We = np.asarray(staleness_weights(W, v, ready, cfg))
    # row 0's neighbors lag 6 rounds: 1/(1+6) of the base weight
    np.testing.assert_allclose(We[0, 1], W[0, 1] / 7.0, rtol=1e-6)
    # the removed mass went to the diagonal
    np.testing.assert_allclose(We[0, 0],
                               W[0, 0] + 2 * (W[0, 1] - W[0, 1] / 7.0),
                               rtol=1e-6)
    # neighbors of client 0 see it as FRESH (it leads): full weight
    np.testing.assert_allclose(We[1, 0], W[1, 0], rtol=1e-6)


def test_no_staleness_is_bitwise_identity():
    cfg = AsyncConfig()
    W = jnp.asarray(MixingSpec.ring(M, self_weight=0.5).W, jnp.float32)
    We = staleness_weights(W, jnp.full((M,), 3, jnp.int32),
                           jnp.ones((M,), jnp.float32), cfg)
    np.testing.assert_array_equal(np.asarray(We), np.asarray(W))


# ---------------------------------------------------------------------------
# Staleness-adaptive local learning rate (eta_staleness_decay)
# ---------------------------------------------------------------------------

def test_staleness_eta_scales_by_lag():
    """Laggards train with a damped step: eta_i = eta/(1+decay*lag_i);
    fresh clients keep EXACTLY eta (lag 0 -> divide by exactly 1), and
    decay=0 is the identity for any version pattern."""
    version = jnp.asarray([5, 5, 3, 0], jnp.int32)
    etas = np.asarray(staleness_eta(0.1, version, 0.5))
    np.testing.assert_allclose(
        etas, [0.1, 0.1, 0.1 / 2.0, 0.1 / 3.5], rtol=1e-6)
    assert etas[0] == np.float32(0.1)            # lag 0: bitwise eta
    assert (np.asarray(staleness_eta(0.1, version, 0.0))
            == np.float32(0.1)).all()
    # monotone: more lag, (weakly) smaller step
    assert (np.diff(etas[1:]) < 0).all()


def test_eta_decay_keeps_event_rows_stochastic():
    """The eta adaptation must compose with the staleness WEIGHT
    discount without touching it: enabling the decay leaves W_eff
    BITWISE unchanged (it only scales local training steps), and the
    rows stay stochastic with non-negative entries."""
    W = jnp.asarray(MixingSpec.ring(M, self_weight=0.5).W, jnp.float32)
    version = jnp.asarray([9, 2, 5, 0, 7, 7, 1, 4], jnp.int32)
    ready = jnp.ones((M,), jnp.float32)
    We_off = np.asarray(staleness_weights(
        W, version, ready, AsyncConfig(max_staleness=4)))
    We_on = np.asarray(staleness_weights(
        W, version, ready,
        AsyncConfig(max_staleness=4, eta_staleness_decay=0.7)))
    np.testing.assert_array_equal(We_on, We_off)
    np.testing.assert_allclose(We_on.sum(axis=1), 1.0, atol=1e-6)
    assert (We_on >= -1e-7).all()


def test_eta_decay_constant_speed_still_bit_identical_to_sync():
    """Zero lag scales eta by exactly 1: a constant-speed async run WITH
    the decay enabled reproduces the synchronous round step bit for bit
    (the adaptive-eta graph computes eta/(1+decay*0) == eta)."""
    _, loss_fn, batches = quad_problem()
    cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4)
    acfg = AsyncConfig(speed=SpeedModel.constant(), eta_staleness_decay=0.9)
    sched = TopologySchedule.edge_sample(ring_graph(M), 0.6)
    step_s = jax.jit(make_round_step(loss_fn, cfg, sched))
    step_a = jax.jit(make_round_step(loss_fn, cfg, sched, async_cfg=acfg))
    st_s = init_round_state({"w": jnp.zeros((M, D))}, jax.random.PRNGKey(7))
    st_a = init_async_state({"w": jnp.zeros((M, D))},
                            jax.random.PRNGKey(7), acfg.speed)
    for _ in range(4):
        st_s, _ = step_s(st_s, batches)
        st_a, _ = step_a(st_a, batches)
    np.testing.assert_array_equal(np.asarray(st_s.params["w"]),
                                  np.asarray(st_a.params["w"]))


def test_eta_decay_works_with_fused_momentum_update():
    """The per-client adaptive eta is a TRACED scalar AND a runtime
    operand of the fused Pallas momentum kernel — the decay branch runs
    the SAME kernel as the fixed-eta path (no XLA fallback, asserted on
    the jaxpr) and matches the plain-update trajectory to ~ulp."""
    from repro.kernels.ops import make_fused_momentum_update
    _, loss_fn, batches = quad_problem()
    cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4)
    acfg = AsyncConfig(speed=SpeedModel.straggler(factor=4.0),
                       eta_staleness_decay=0.1)
    spec = MixingSpec.ring(M, self_weight=0.5)
    step_f = jax.jit(make_round_step(
        loss_fn, cfg, spec, async_cfg=acfg,
        fused_update=make_fused_momentum_update(interpret=True)))
    step_x = jax.jit(make_round_step(loss_fn, cfg, spec, async_cfg=acfg))
    st0 = init_async_state({"w": jnp.zeros((M, D))}, jax.random.PRNGKey(0),
                           acfg.speed)
    jaxpr = jax.make_jaxpr(step_f)(st0, batches)
    assert "pallas_call" in str(jaxpr), (
        "traced-eta async path fell off the Pallas momentum kernel")
    st_f, st_x = st0, st0
    for _ in range(3):
        st_f, _ = step_f(st_f, batches)
        st_x, _ = step_x(st_x, batches)
    w_f = np.asarray(st_f.params["w"])
    assert np.isfinite(w_f).all()
    np.testing.assert_allclose(w_f, np.asarray(st_x.params["w"]),
                               atol=1e-6)


def test_eta_decay_damps_stragglers():
    """Under a straggler tail the adaptive eta changes the trajectory
    (laggards really do train smaller steps) while staying finite, and
    the config validates."""
    _, loss_fn, batches = quad_problem()
    cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4)
    spec = MixingSpec.ring(M, self_weight=0.5)
    runs = {}
    for decay in (0.0, 1.0):
        acfg = AsyncConfig(speed=SpeedModel.straggler(factor=10.0),
                           max_staleness=6, eta_staleness_decay=decay)
        step = jax.jit(make_round_step(loss_fn, cfg, spec, async_cfg=acfg))
        st = init_async_state({"w": jnp.zeros((M, D))},
                              jax.random.PRNGKey(5), acfg.speed)
        for _ in range(2 * M):
            st, _ = step(st, batches)
        runs[decay] = np.asarray(st.params["w"])
        assert np.isfinite(runs[decay]).all()
    assert not np.array_equal(runs[0.0], runs[1.0])
    with pytest.raises(ValueError, match="eta_staleness_decay"):
        AsyncConfig(eta_staleness_decay=-0.1)


# ---------------------------------------------------------------------------
# Zero-delay equivalence: constant-speed async == synchronous DFedAvgM
# ---------------------------------------------------------------------------

def _topologies():
    ring = MixingSpec.ring(M, self_weight=0.5)
    return [("static_ring", ring),
            ("constant", TopologySchedule.constant(ring)),
            ("edge_sample",
             TopologySchedule.edge_sample(ring_graph(M), 0.6)),
            ("cycle", TopologySchedule.cycle(
                [ring, MixingSpec.torus(2, M // 2)]))]


@pytest.mark.parametrize("quant", [None, QuantConfig(bits=8)],
                         ids=["fp32", "q8-stoch"])
@pytest.mark.parametrize("topo", [t for _, t in _topologies()],
                         ids=[n for n, _ in _topologies()])
def test_zero_delay_async_bit_identical_to_sync(topo, quant):
    _, loss_fn, batches = quad_problem()
    cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4, quant=quant)
    acfg = AsyncConfig(speed=SpeedModel.constant())
    step_s = jax.jit(make_round_step(loss_fn, cfg, topo))
    step_a = jax.jit(make_round_step(loss_fn, cfg, topo, async_cfg=acfg))
    st_s = init_round_state({"w": jnp.zeros((M, D))}, jax.random.PRNGKey(7))
    st_a = init_async_state({"w": jnp.zeros((M, D))},
                            jax.random.PRNGKey(7), acfg.speed)
    for _ in range(4):
        st_s, _ = step_s(st_s, batches)
        st_a, mt = step_a(st_a, batches)
    np.testing.assert_array_equal(np.asarray(st_s.params["w"]),
                                  np.asarray(st_a.params["w"]))
    assert float(mt["ready_frac"]) == 1.0
    assert int(st_a.round) == 4 and np.asarray(st_a.version).min() == 4


def test_scan_engine_bit_identical_to_event_loop():
    _, loss_fn, batches = quad_problem()
    cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4)
    spec = MixingSpec.ring(M, self_weight=0.5)
    acfg = AsyncConfig(speed=SpeedModel.straggler(factor=5.0))
    step = jax.jit(make_round_step(loss_fn, cfg, spec, async_cfg=acfg))
    st1 = init_async_state({"w": jnp.zeros((M, D))},
                           jax.random.PRNGKey(3), acfg.speed)
    n_events = 6
    for _ in range(n_events):
        st1, _ = step(st1, batches)
    engine = jax.jit(make_async_engine(loss_fn, cfg, spec, acfg))
    st2 = init_async_state({"w": jnp.zeros((M, D))},
                           jax.random.PRNGKey(3), acfg.speed)
    stacked = jax.tree.map(
        lambda b: jnp.broadcast_to(b[None], (n_events,) + b.shape), batches)
    st2, metrics = engine(st2, stacked)
    np.testing.assert_array_equal(np.asarray(st1.params["w"]),
                                  np.asarray(st2.params["w"]))
    assert metrics["clock"].shape == (n_events,)
    assert (np.diff(np.asarray(metrics["clock"])) >= 0).all()


def test_straggler_develops_staleness_and_stays_finite():
    _, loss_fn, batches = quad_problem()
    cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4)
    spec = MixingSpec.ring(M, self_weight=0.5)
    acfg = AsyncConfig(speed=SpeedModel.straggler(factor=10.0),
                       max_staleness=6)
    step = jax.jit(make_round_step(loss_fn, cfg, spec, async_cfg=acfg))
    st = init_async_state({"w": jnp.zeros((M, D))},
                          jax.random.PRNGKey(5), acfg.speed)
    for _ in range(3 * M):
        st, mt = step(st, batches)
    version = np.asarray(st.version)
    assert version[0] < version[1:].min(), "straggler should lag the fleet"
    assert int(mt["max_staleness"]) > 0
    assert np.isfinite(np.asarray(st.params["w"])).all()
    assert float(st.clock) > 0


def test_async_rejects_stateful_schedules():
    sched = TopologySchedule.random_walk(ring_graph(M), stateful=True)
    _, loss_fn, _ = quad_problem()
    with pytest.raises(ValueError, match="stateful"):
        make_round_step(loss_fn, DFedAvgMConfig(), sched,
                        async_cfg=AsyncConfig())


# ---------------------------------------------------------------------------
# Satellite: compute-skip for statically-sized participation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sched_fn", [
    lambda: TopologySchedule.partial(ring_graph(M), 0.5, exact=True),
    lambda: TopologySchedule.random_walk(ring_graph(M), horizon=32, seed=1),
], ids=["exact_partial", "random_walk"])
def test_skip_inactive_compute_same_numerics(sched_fn):
    sched = sched_fn()
    assert sched.static_active_count is not None
    _, loss_fn, batches = quad_problem()
    cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4)
    step_skip = jax.jit(make_round_step(loss_fn, cfg, sched))  # auto: on
    step_full = jax.jit(make_round_step(loss_fn, cfg, sched,
                                        skip_inactive_compute=False))
    s1 = init_round_state({"w": jnp.zeros((M, D))}, jax.random.PRNGKey(9))
    s2 = init_round_state({"w": jnp.zeros((M, D))}, jax.random.PRNGKey(9))
    for _ in range(4):
        s1, m1 = step_skip(s1, batches)
        s2, m2 = step_full(s2, batches)
    np.testing.assert_allclose(np.asarray(s1.params["w"]),
                               np.asarray(s2.params["w"]),
                               rtol=0, atol=1e-6)
    assert float(m1["active_frac"]) == float(m2["active_frac"])
    # "loss" means the same thing with skip on or off: the mean over
    # clients that participated this round
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-6)


def test_skip_inactive_compute_reduces_flops():
    from repro.launch.hlo_stats import traced_flops
    params, loss_fn, batches = dot_problem()
    cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4)
    st = init_round_state(params, jax.random.PRNGKey(0))
    sched = TopologySchedule.random_walk(ring_graph(M), horizon=32, seed=1)
    f_skip = traced_flops(make_round_step(loss_fn, cfg, sched), st, batches)
    f_full = traced_flops(
        make_round_step(loss_fn, cfg, sched, skip_inactive_compute=False),
        st, batches)
    # 2 of 8 lanes train: local-SGD FLOPs drop ~4x; overhead caps the win.
    assert f_skip < 0.6 * f_full, (f_skip, f_full)


def test_skip_requires_static_count():
    _, loss_fn, _ = quad_problem()
    sched = TopologySchedule.partial(ring_graph(M), 0.5)   # i.i.d.: dynamic
    with pytest.raises(ValueError, match="statically bounded"):
        make_round_step(loss_fn, DFedAvgMConfig(), sched,
                        skip_inactive_compute=True)


# ---------------------------------------------------------------------------
# Satellite: padded upper-bound gather for capped i.i.d. participation
# ---------------------------------------------------------------------------

def test_capped_partial_respects_static_bound():
    """cap_slack turns the i.i.d. draw into a statically bounded one: no
    round ever exceeds the cap, and the schedule advertises it."""
    sched = TopologySchedule.partial(ring_graph(M), 0.5, cap_slack=1)
    cap = int(np.ceil(0.5 * M)) + 1
    assert sched.static_active_count == cap
    for t in range(40):
        W, active = sched.sample_w(jax.random.PRNGKey(t), t)
        n_act = int(np.asarray(active).sum())
        assert n_act <= cap
        W = np.asarray(W, np.float64)
        assert np.allclose(W.sum(axis=1), 1.0, atol=1e-6)
        assert np.allclose(W, W.T, atol=1e-6)
        # inactive rows degenerate to e_i
        for i in np.nonzero(np.asarray(active) == 0)[0]:
            assert W[i, i] == pytest.approx(1.0)
    with pytest.raises(ValueError, match="cap_slack"):
        TopologySchedule.partial(ring_graph(M), 0.5, exact=True,
                                 cap_slack=1)


def test_capped_partial_padded_gather_same_numerics():
    """The padded gather (out-of-bounds fill slots, drop-mode scatter) is
    exact: skip on == skip off, params and metrics, even on rounds with
    fewer actives than the cap."""
    _, loss_fn, batches = quad_problem()
    sched = TopologySchedule.partial(ring_graph(M), 0.5, cap_slack=2)
    assert sched.static_active_count < M
    cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4)
    step_skip = jax.jit(make_round_step(loss_fn, cfg, sched))  # auto: on
    step_full = jax.jit(make_round_step(loss_fn, cfg, sched,
                                        skip_inactive_compute=False))
    s1 = init_round_state({"w": jnp.zeros((M, D))}, jax.random.PRNGKey(9))
    s2 = init_round_state({"w": jnp.zeros((M, D))}, jax.random.PRNGKey(9))
    for _ in range(6):
        s1, m1 = step_skip(s1, batches)
        s2, m2 = step_full(s2, batches)
        assert float(m1["active_frac"]) == float(m2["active_frac"])
        assert float(m1["loss"]) == pytest.approx(float(m2["loss"]),
                                                  rel=1e-6)
    np.testing.assert_allclose(np.asarray(s1.params["w"]),
                               np.asarray(s2.params["w"]),
                               rtol=0, atol=1e-6)


def test_capped_partial_skip_reduces_flops():
    """The ROADMAP follow-up: i.i.d. participation now skips inactive
    lanes' local SGD too — ~cap/m of the FLOPs, visible in the HLO."""
    from repro.launch.hlo_stats import traced_flops
    params, loss_fn, batches = dot_problem()
    cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4)
    st = init_round_state(params, jax.random.PRNGKey(0))
    sched = TopologySchedule.partial(ring_graph(M), 0.25, cap_slack=1)
    assert sched.static_active_count == 3
    f_skip = traced_flops(make_round_step(loss_fn, cfg, sched), st, batches)
    f_full = traced_flops(
        make_round_step(loss_fn, cfg, sched, skip_inactive_compute=False),
        st, batches)
    # 3 of 8 lanes train: local-SGD FLOPs drop ~2.7x; overhead caps it.
    assert f_skip < 0.7 * f_full, (f_skip, f_full)


def test_async_ready_capacity_same_numerics():
    """The async analogue of the padded gather: with ``ready_capacity``
    set, each event trains only (up to) cap gathered ready lanes instead
    of vmapping local SGD over all m — and the trajectory is the
    full-width engine's, because overflow lanes keep their elapsed clocks
    and fire in immediately-following zero-duration events (graceful
    event splitting, not dropped work). The event schedule (ready
    fraction, clock) is exact; the two engines are separately compiled
    programs, so their floats agree to a few ulp (XLA picks FMA
    contraction per module), as in the other parity tests."""
    from repro.core import make_async_round_step
    params, loss_fn, batches = dot_problem()
    cfg = DFedAvgMConfig(eta=0.05, theta=0.9, local_steps=4)
    spec = MixingSpec.ring(M, self_weight=0.5)
    speed = SpeedModel.lognormal(mean=3.0, sigma=0.5)
    full = jax.jit(make_async_round_step(loss_fn, cfg, spec,
                                         AsyncConfig(speed=speed)))
    skip = jax.jit(make_async_round_step(
        loss_fn, cfg, spec, AsyncConfig(speed=speed, ready_capacity=1)))
    s1 = init_async_state(params, jax.random.PRNGKey(0), speed)
    s2 = init_async_state(params, jax.random.PRNGKey(0), speed)
    eps = float(np.finfo(np.float32).eps)
    for _ in range(12):
        s1, m1 = full(s1, batches)
        s2, m2 = skip(s2, batches)
        l1 = float(m1["loss"])
        assert abs(l1 - float(m2["loss"])) <= 8 * eps * (abs(l1) + 1.0)
        assert float(m1["ready_frac"]) == float(m2["ready_frac"])
        for a, b in zip(jax.tree.leaves(s1.params),
                        jax.tree.leaves(s2.params)):
            a, b = np.asarray(a), np.asarray(b)
            tol = 8 * eps * (float(np.abs(a).max()) + 1.0)
            np.testing.assert_allclose(b, a, rtol=0, atol=tol)
    assert float(s1.clock) == float(s2.clock)


def test_async_ready_capacity_reduces_flops():
    """The pool-scale claim: the capacity-gathered event step's local SGD
    costs ~cap/m of the full vmap — visible in traced FLOPs (mixer and
    bookkeeping overhead bound the ratio away from cap/m at toy size)."""
    from repro.core import make_async_round_step
    from repro.launch.hlo_stats import traced_flops
    params, loss_fn, batches = dot_problem()
    cfg = DFedAvgMConfig(eta=0.05, theta=0.9, local_steps=4)
    spec = MixingSpec.ring(M, self_weight=0.5)
    speed = SpeedModel.lognormal(mean=3.0, sigma=0.5)
    st = init_async_state(params, jax.random.PRNGKey(0), speed)
    f_full = traced_flops(
        make_async_round_step(loss_fn, cfg, spec, AsyncConfig(speed=speed)),
        st, batches)
    f_skip = traced_flops(
        make_async_round_step(loss_fn, cfg, spec,
                              AsyncConfig(speed=speed, ready_capacity=1)),
        st, batches)
    # 1 of 8 lanes trains per event
    assert f_skip < 0.5 * f_full, (f_skip, f_full)


def test_async_ready_capacity_validates():
    with pytest.raises(ValueError, match="ready_capacity"):
        AsyncConfig(speed=SpeedModel.constant(), ready_capacity=0)


def test_exact_partial_cohort_size_is_exact():
    sched = TopologySchedule.partial(ring_graph(M), 0.5, exact=True)
    assert sched.static_active_count == 4
    for t in range(5):
        W, active = sched.sample_w(jax.random.PRNGKey(t), t)
        assert int(np.asarray(active).sum()) == 4
        W = np.asarray(W, np.float64)
        assert np.allclose(W.sum(axis=1), 1.0, atol=1e-6)
        assert np.allclose(W, W.T, atol=1e-6)
    # expectation accounting matches the without-replacement cohort draw
    exp = sched.expected_directed_edges()
    assert exp == pytest.approx(4 * 3 / (M * (M - 1)) * 2 * M)


# ---------------------------------------------------------------------------
# Satellite: stateful random-walk token through RoundState
# ---------------------------------------------------------------------------

def test_stateful_walk_token_is_in_graph_state():
    sched = TopologySchedule.random_walk(ring_graph(M), stateful=True,
                                         start=3)
    assert sched.is_stateful and sched.walk is None
    _, loss_fn, batches = quad_problem()
    cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4)
    step = jax.jit(make_round_step(loss_fn, cfg, sched))
    st = init_round_state({"w": jnp.zeros((M, D))}, jax.random.PRNGKey(5),
                          token=sched.init_token())
    assert int(st.token) == 3
    adj = np.asarray(ring_graph(M).adj)
    prev = int(st.token)
    for _ in range(8):
        st, mt = step(st, batches)
        cur = int(st.token)
        assert adj[prev, cur], "token must move along a base-graph edge"
        prev = cur
    assert float(mt["active_frac"]) == 2.0 / M


def test_stateful_walk_needs_token_seed():
    sched = TopologySchedule.random_walk(ring_graph(M), stateful=True)
    _, loss_fn, batches = quad_problem()
    step = make_round_step(loss_fn, DFedAvgMConfig(), sched)
    st = init_round_state({"w": jnp.zeros((M, D))}, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="init_token"):
        step(st, batches)
    with pytest.raises(ValueError, match="precomputed"):
        sched.sample_w(jax.random.PRNGKey(0), 0)


def test_stateful_walk_event_is_valid_pairwise_average():
    sched = TopologySchedule.random_walk(ring_graph(M), stateful=True)
    W, active, key_q, nxt = jax.jit(sched.token_event)(
        jax.random.PRNGKey(2), jnp.asarray(0, jnp.int32))
    W = np.asarray(W, np.float64)
    assert np.allclose(W.sum(axis=1), 1.0, atol=1e-6)
    assert np.allclose(W, W.T, atol=1e-6)
    assert int(np.asarray(active).sum()) == 2
    assert int(nxt) in (1, M - 1)


# ---------------------------------------------------------------------------
# Satellite: per-member cycle plans + billing
# ---------------------------------------------------------------------------

def test_cycle_member_plans_drop_union_wire():
    a = MixingSpec.dense(chain_from_order([0, 1, 2, 3, 4, 5, 6, 7]))
    b = MixingSpec.dense(chain_from_order([1, 3, 0, 5, 2, 7, 4, 6]))
    cyc = TopologySchedule.cycle([a, b])
    plans = cyc.gossip_plans()
    union = cyc.gossip_plan()
    assert len(plans) == 2
    # members are edge-disjoint: union moves BOTH members' wire each round
    assert union.num_directed_wire_edges == sum(
        p.num_directed_wire_edges for p in plans)
    d = 1000
    per_round = plan_round_bits(plans, d, None)
    assert per_round == pytest.approx(
        plan_round_bits(union, d, None) / 2)
    assert plan_round_bits(plans, d, None, t=1) == \
        plan_round_bits(plans[1], d, None)
    # each member plan reconstructs exactly its own matrix
    np.testing.assert_allclose(plans[0].as_matrix(), a.W, atol=1e-12)
    np.testing.assert_allclose(plans[1].as_matrix(), b.W, atol=1e-12)
    # non-cycle schedules: gossip_plans is just [gossip_plan]
    es = TopologySchedule.edge_sample(ring_graph(M), 0.5)
    assert len(es.gossip_plans()) == 1


# ---------------------------------------------------------------------------
# Billing: realized async bytes
# ---------------------------------------------------------------------------

def test_async_event_bits_and_ledger():
    """One billing convention: an event bills its realized live directed
    edges, whatever backend executed the mix (the sparse plan wire is a
    diagnostic, not the bill — see plan_round_bits)."""
    d = 100
    assert async_event_bits(d, None, live_edges=4) == 32 * d * 4
    q = QuantConfig(bits=8)
    assert async_event_bits(d, q, live_edges=3) == (32 + 8 * d) * 3
    with pytest.raises(ValueError):
        async_event_bits(d, None)
    led = CommLedger(0.0)
    led.add_bits(1000.0)
    led.add_bits(500.0)
    assert led.total_bits == 1500.0
    # mixed use: per-round billing still composes with per-event extras
    led2 = CommLedger(100.0)
    led2.tick(3)
    led2.add_bits(50.0)
    assert led2.total_bits == 350.0


def test_async_live_edges_metric_bills_realized_edges():
    _, loss_fn, batches = quad_problem()
    cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4)
    spec = MixingSpec.ring(M, self_weight=0.5)
    acfg = AsyncConfig(speed=SpeedModel.constant())
    step = jax.jit(make_round_step(loss_fn, cfg, spec, async_cfg=acfg))
    st = init_async_state({"w": jnp.zeros((M, D))}, jax.random.PRNGKey(0),
                          acfg.speed)
    _, mt = step(st, batches)
    # constant speed, no staleness: every ring edge is live
    assert int(mt["live_edges"]) == 2 * M


# ---------------------------------------------------------------------------
# Hypothesis sweep (guarded: bare environments skip, CI runs it)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @settings(deadline=None, max_examples=50)
    @given(seed=st.integers(0, 10_000), max_staleness=st.integers(0, 8),
           discount=st.sampled_from(["inverse", "power"]),
           gamma=st.floats(0.1, 1.0))
    def test_property_staleness_rows_stay_stochastic(seed, max_staleness,
                                                     discount, gamma):
        """Any version/ready pattern over any sampled W_t: the reweighted
        event matrix keeps stochastic rows, support containment, and
        identity rows for busy clients."""
        cfg = AsyncConfig(max_staleness=max_staleness, discount=discount,
                          gamma=gamma)
        rng = np.random.default_rng(seed)
        sched = TopologySchedule.edge_sample(ring_graph(M), 0.6)
        W, _ = sched.sample_w(jax.random.PRNGKey(seed), 0)
        version = jnp.asarray(rng.integers(0, 12, size=M), jnp.int32)
        ready = jnp.asarray(rng.integers(0, 2, size=M), jnp.float32)
        We = np.asarray(staleness_weights(W, version, ready, cfg))
        _check_event_matrix(We, np.asarray(W), ready)
