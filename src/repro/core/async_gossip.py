"""Event-driven asynchronous DFedAvgM with staleness-aware mixing.

The paper's Algorithms 1/2 put a *global round barrier* between local SGD
and gossip: no pair mixes until every client has finished its K local
steps, so each round costs the fleet ``max_i duration_i`` — under a heavy
straggler tail nearly all clients sit idle. This subsystem drops the
barrier (DeceFL arXiv:2107.07171 / AD-PSGD flavor, built on the
time-varying ``TopologySchedule`` machinery):

  * every client draws its compute duration from a pluggable
    :class:`~repro.core.event_clock.SpeedModel` and finishes local SGD on
    its own virtual clock;
  * an *event* fires when the earliest client(s) finish: they mix
    immediately with their graph neighbors' *currently published*
    parameters, while busy clients keep computing and hold theirs;
  * a neighbor's published parameters may be **stale** — ``version[j]``
    counts client j's completed local rounds, and the mixing weight on a
    neighbor lagging ``s = version[i] - version[j]`` rounds is discounted
    by ``rho(s)`` (``1/(1+s)`` or ``gamma^s``, hard-zeroed beyond
    ``max_staleness``), with the removed mass folded back into the self
    weight so every row stays stochastic (:func:`staleness_weights`).

The engine is fully in-graph: the "event queue" is the vector of
per-client next-ready times carried in :class:`AsyncRoundState`, one event
is one :func:`make_async_round_step` application, and
:func:`make_async_engine` runs a whole queue of events as a single
``lax.scan``. Mixing lowers through the same backends as the synchronous
path — the dense einsum reference or the compiled ``GossipPlan`` sparse
masked-ppermute collective (``make_event_mixer``, which shares the flat wire-buffer path with the
synchronous engine) — and per-event realized live-edge bytes are billed
via ``CommLedger`` (`repro.core.comm_cost.async_event_bits`, the same
backend-independent convention as the synchronous ledger).

Degenerate case pinned by tests: under a **constant** speed model every
client finishes every event simultaneously, staleness never develops, and
the engine reproduces synchronous ``make_round_step`` — *bit for bit* in
fp32 (the PRNG chain, weight matrices, and collectives are identical);
the quantized flat-wire body additionally carries ~1 ulp/round of XLA
module-level fusion rounding (the wire words themselves are identical).

Asynchrony changes the algorithm: the realized mixing matrices are
row-stochastic but no longer symmetric, so Theorem 1 does not literally
apply — convergence follows the time-varying/asynchronous analyses of the
follow-up papers. ``benchmarks/bench_async.py`` measures the payoff:
virtual wall-clock to a target loss under a straggler tail.

Invariants (pinned by ``tests/test_async_gossip.py`` and relied on by the
pooled execution mode, ``core.client_pool``):

  * ROW-STOCHASTICITY UNDER THE STALENESS CUTOFF: for any base
    row-stochastic ``W``, :func:`staleness_weights` keeps every row
    summing to 1 with non-negative entries — discounted off-diagonal mass
    folds into the self weight, and rows of non-ready clients degenerate
    to ``e_i`` (they hold their parameters exactly, bit for bit).
  * VERSION MONOTONICITY: ``version[i]`` increments exactly when client
    i's clock fires AND the schedule lets it participate — it never
    decreases and never changes outside i's own events. Data pipelines
    must key on it (``batch_fn``), never on the global event index.
  * SUPPORT CONTAINMENT: ``W_eff``'s off-diagonal support is a subset of
    the base topology's — staleness only *removes* edges, so the sparse
    backend's compiled wire schedule stays valid for every event.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from .dfedavgm import DFedAvgMConfig
from .event_clock import SpeedModel, next_event
from .local_sgd import local_train
from .mixing import consensus_distance, make_event_mixer
from .topology import MixingSpec, TopologySchedule

Pytree = Any
LossFn = Callable[..., jnp.ndarray]

__all__ = ["AsyncConfig", "AsyncRoundState", "init_async_state",
           "staleness_weights", "staleness_eta", "make_async_round_step",
           "make_async_engine"]

# Salt folded into the model key to derive the independent clock-PRNG
# chain; any constant works, it just must not collide with a split index.
_CLOCK_SALT = 0x61737963  # "asyc"


@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    """Asynchronous-engine knobs (the algorithmic hyper-parameters stay in
    :class:`~repro.core.dfedavgm.DFedAvgMConfig`).

    speed:         per-client compute-duration distribution.
    max_staleness: neighbors more than this many local rounds behind get
                   mixing weight 0 (their mass folds into the self
                   weight).
    discount:      staleness discount rho(s): "inverse" -> 1/(1+s),
                   "power" -> gamma**s. rho(0) == 1 exactly, so fresh
                   neighbors are never downweighted.
    gamma:         base of the "power" discount.
    eta_staleness_decay:
                   staleness-ADAPTIVE local learning rate: client i's
                   local-SGD eta is scaled to ``eta / (1 + decay * lag_i)``
                   with ``lag_i = max_j version[j] - version[i]`` (how many
                   local rounds i trails the freshest client) — a lagging
                   client's big catch-up gradient is damped instead of
                   slamming stale parameters into the mix (cf. the
                   staleness discount on the WEIGHTS, which this composes
                   with). 0 disables; with zero lag (constant speed) the
                   scale is exactly 1, so the sync-reproduction guarantee
                   is untouched (see :func:`staleness_eta`). The per-client
                   eta is traced; the fused Pallas momentum kernel takes
                   eta/theta as RUNTIME scalar operands, so the decayed
                   path runs the same kernel as the fixed-eta path (the
                   client vmap batches the scalar block) — no XLA
                   fallback, no retrace per eta value.
    ready_capacity:
                   compute-skip bound for pool-scale fleets: the event
                   step gathers at most this many READY lanes, trains
                   only them (~``ready_capacity/m`` of the full-fleet
                   local-SGD FLOPs, the padded gather/scatter of the
                   synchronous partial-participation path), and scatters
                   the results back. An event whose ready set overflows
                   the capacity trains the first ``ready_capacity`` ready
                   lanes and DEFERS the rest: their clocks are not
                   redrawn, so they remain the queue minimum and fire in
                   the immediately following zero-duration event —
                   nothing is dropped, the event just splits. ``None``
                   (default) trains every lane, the exact legacy graph.
                   With continuous speed models ties have measure zero
                   and the typical event has ONE finisher, so
                   ``ready_capacity=1`` is the natural pool setting
                   (constant speed fires all m at once — leave this None
                   there, or accept the m-way event split).
    """

    speed: SpeedModel = SpeedModel.constant()
    max_staleness: int = 8
    discount: str = "inverse"   # inverse | power
    gamma: float = 0.5
    eta_staleness_decay: float = 0.0
    ready_capacity: int | None = None

    def __post_init__(self):
        if self.discount not in ("inverse", "power"):
            raise ValueError(f"unknown staleness discount "
                             f"{self.discount!r}; allowed: inverse | power")
        if self.max_staleness < 0:
            raise ValueError("max_staleness must be >= 0")
        if not 0.0 < self.gamma <= 1.0:
            raise ValueError("need 0 < gamma <= 1")
        if self.eta_staleness_decay < 0.0:
            raise ValueError("need eta_staleness_decay >= 0")
        if self.ready_capacity is not None and self.ready_capacity < 1:
            raise ValueError("need ready_capacity >= 1 (or None)")


class AsyncRoundState(NamedTuple):
    """``RoundState`` extended with the event clock. ``params``/``rng``/
    ``round`` keep their synchronous meaning (``round`` counts *events*),
    so checkpointing and schedule indexing work unchanged."""

    params: Pytree        # stacked client copies, leaves [m, ...]
    rng: jax.Array        # model-randomness chain (same as RoundState.rng)
    round: jnp.ndarray    # int32 event counter
    clock: jnp.ndarray    # f32 scalar — virtual time of the last event
    next_ready: jax.Array  # [m] f32 — the event queue: per-client finish times
    version: jax.Array    # [m] int32 — completed local rounds (staleness base)
    clock_rng: jax.Array  # duration-randomness chain, independent of `rng`


def init_async_state(params_stacked: Pytree, key: jax.Array,
                     speed: SpeedModel) -> AsyncRoundState:
    """``key`` seeds the MODEL chain exactly like ``init_round_state`` (so
    a constant-speed async run is bit-identical to the sync run seeded
    with the same key); the clock chain is derived by salting it."""
    m = jax.tree.leaves(params_stacked)[0].shape[0]
    k_dur, clock_rng = jax.random.split(
        jax.random.fold_in(key, _CLOCK_SALT))
    return AsyncRoundState(
        params=params_stacked, rng=key,
        round=jnp.zeros((), jnp.int32),
        clock=jnp.zeros((), jnp.float32),
        next_ready=speed.draw(k_dur, m),
        version=jnp.zeros((m,), jnp.int32),
        clock_rng=clock_rng)


def _discount(s, cfg: AsyncConfig):
    rho = (1.0 / (1.0 + s.astype(jnp.float32)) if cfg.discount == "inverse"
           else jnp.power(cfg.gamma, s.astype(jnp.float32)))
    return jnp.where(s <= cfg.max_staleness, rho, 0.0)


def staleness_weights(W, version, ready, cfg: AsyncConfig) -> jnp.ndarray:
    """Staleness-reweighted event matrix ``W_eff`` from a base mixing
    matrix ``W`` (possibly traced).

    For each READY row i, off-diagonal weight on neighbor j becomes
    ``W[i,j] * rho(s_ij)`` with ``s_ij = max(version[i] - version[j], 0)``
    (how many local rounds j lags i); the removed mass is folded back into
    the self weight, so the row still sums to 1 with non-negative entries
    whenever ``W``'s row did. Non-ready rows become ``e_i`` (busy clients
    hold their parameters). When no neighbor is stale (``rho == 1``
    everywhere) the computation is the identity ``W - 0 + diag(0)`` — the
    constant-speed path stays bit-identical to the synchronous mixer.

    The result is row-stochastic but NOT symmetric: the staleness pattern
    breaks Definition 1's symmetry, which is inherent to asynchrony (the
    property tests pin row-stochasticity + support containment instead).
    """
    Wj = jnp.asarray(W, jnp.float32)
    m = Wj.shape[0]
    eye = jnp.eye(m, dtype=jnp.float32)
    s = jnp.maximum(version[:, None] - version[None, :], 0)
    removed = Wj * (1.0 - eye) * (1.0 - _discount(s, cfg))
    W_eff = Wj - removed + jnp.diag(removed.sum(axis=1))
    ready = jnp.asarray(ready, jnp.float32)
    return jnp.where(ready[:, None] > 0, W_eff, eye)


def staleness_eta(eta: float, version, decay: float) -> jnp.ndarray:
    """Per-client staleness-adaptive local learning rate [m]:

        eta_i = eta / (1 + decay * lag_i),
        lag_i = max_j version[j] - version[i]

    A client ``lag_i`` local rounds behind the freshest trains with a
    proportionally damped step, so its catch-up gradient (computed on
    stale parameters) cannot overshoot when it finally mixes. ``lag == 0``
    scales by exactly ``1/(1+0) == 1`` — under a constant speed model
    every client stays at ``eta`` bit for bit, preserving the async ==
    sync reproduction guarantee. ``decay == 0`` is the identity.
    """
    lag = (jnp.max(version) - version).astype(jnp.float32)
    return jnp.float32(eta) / (1.0 + jnp.float32(decay) * lag)


def make_async_round_step(loss_fn: LossFn, cfg: DFedAvgMConfig,
                          spec: MixingSpec | TopologySchedule,
                          async_cfg: AsyncConfig,
                          mesh=None, client_axes: Sequence[str] = (),
                          param_specs: Pytree | None = None,
                          fused_update=None,
                          with_metrics: bool = True,
                          with_telemetry: bool = False,
                          batch_fn: Callable | None = None) -> Callable:
    """Build event_step(state: AsyncRoundState, batches) -> (state',
    metrics) — ONE event of the asynchronous engine (the unit
    :func:`make_async_engine` scans over; also the drop-in round step
    ``make_round_step(..., async_cfg=...)`` returns).

    ``batches`` keeps the synchronous layout (leaves [m, K, ...]): the
    simulation trains every client's lane each event and the event's
    ready mask selects whose fresh ``z`` enters the mix — busy clients'
    lanes are discarded exactly like the synchronous partial-participation
    path (their published params, which only ever change at their OWN
    events, are what neighbors read — so training at the finish event is
    equivalent to having trained over the whole busy interval).
    ``AsyncConfig.ready_capacity`` replaces that full-width vmap with the
    partial-participation path's padded ready-set gather/scatter — only
    ~``ready_capacity/m`` of the local-SGD FLOPs per event (asserted via
    ``traced_flops`` in ``tests/test_async_gossip.py``), which is what
    makes event stepping affordable at pool scale where typically ONE
    client is ready.

    ``spec`` may be a static :class:`MixingSpec` or any non-stateful
    :class:`TopologySchedule` (the event index drives the schedule, and
    the schedule's active mask composes with the clock's ready mask).

    ``with_metrics``: also emit ``consensus_dist``, a cross-client sum
    over the whole model (see ``dfedavgm.make_round_step``); the event's
    ``loss``, ``clock``, ``ready_frac``, ``live_edges`` and staleness
    (``mean_staleness``, ``max_staleness``) are emitted either way.

    ``with_telemetry``: additionally emit ``metrics["telemetry"]`` (a
    :class:`repro.telemetry.Telemetry` pytree): the event's staleness
    HISTOGRAM (per-client version lag, overflow bucket past the hard
    cutoff), the base-support edges the cutoff zeroed (``dropped_edges``
    — ``live_edges + dropped_edges`` conserves the base ready live
    count), realized wire bits, and the quantizer's observed error vs the
    Assumption-4 bound over the event's ready lanes. Default OFF; the
    off path is bit-identical to a build without the flag.

    ``batch_fn``: optional in-graph data pipeline
    ``(client_ids [m], versions [m]) -> batches`` keyed on each client's
    own VERSION counter (e.g. ``repro.data.lm_client_batches``). When
    given, the returned step ignores its ``batches`` argument (pass None)
    and derives each event's data from the pre-event versions — so a
    client's data stream is invariant to how the fleet's events
    interleave. Keying on the global event index instead was a bug: two
    runs differing only in straggler timing fed every client different
    data.
    """
    scheduled = isinstance(spec, TopologySchedule)
    if scheduled and spec.is_stateful:
        raise ValueError("async gossip needs a data-independent schedule; "
                         "use random_walk(stateful=False) whose path does "
                         "not depend on the event clock")
    m = spec.m
    mcfg = cfg.mixer_config()
    impl = mcfg.resolved_impl(spec, mesh, client_axes)
    plan = spec.gossip_plan() if impl in ("ring", "torus", "sparse") else None
    ev = make_event_mixer(m, quant=mcfg.quant, mesh=mesh,
                          client_axes=client_axes, param_specs=param_specs,
                          plan=plan, wire=mcfg.wire, gate=True)
    W_static = None if scheduled else jnp.asarray(spec.W, jnp.float32)
    if with_telemetry:
        from ..telemetry.metrics import (Telemetry, client_dim,
                                         dropped_edge_count,
                                         quant_round_telemetry,
                                         staleness_histogram,
                                         wire_bits_for)

    def event_step(state: AsyncRoundState, batches: Pytree = None):
        key_round, key_mix, key_next = jax.random.split(state.rng, 3)
        client_keys = jax.random.split(key_round, m)

        if batch_fn is not None:
            # Version-keyed pipeline: client i's data depends only on its
            # own pre-event progress counter, not the event index.
            batches = batch_fn(jnp.arange(m, dtype=jnp.int32),
                               state.version)
        elif batches is None:
            raise ValueError("event_step needs batches (or build the step "
                             "with a version-keyed batch_fn)")

        t_now, ready = next_event(state.next_ready)

        if async_cfg.eta_staleness_decay > 0.0:
            # Staleness-adaptive local LR: lagging clients train with a
            # damped step (lag derived from the PRE-event versions; zero
            # lag scales by exactly 1, keeping constant-speed runs bit-
            # identical to the fixed-eta graph's values). eta is a
            # RUNTIME operand of the fused Pallas momentum kernel, so the
            # per-client traced eta runs the same fused update as the
            # fixed-eta path (vmap batches the scalar block) — no XLA
            # fallback.
            etas = staleness_eta(cfg.eta, state.version,
                                 async_cfg.eta_staleness_decay)
            train_one = lambda p, b, k, e: local_train(
                loss_fn, p, b, k, eta=e, theta=cfg.theta,
                fused_update=fused_update)
            train_args = (state.params, batches, client_keys, etas)
        else:
            train_one = lambda p, b, k: local_train(
                loss_fn, p, b, k, eta=cfg.eta, theta=cfg.theta,
                fused_update=fused_update)
            train_args = (state.params, batches, client_keys)

        cap = async_cfg.ready_capacity
        if cap is not None and cap < m:
            # Pool-scale compute skip: train only the ready lanes, via
            # the same padded gather/scatter as the synchronous partial-
            # participation path (see dfedavgm.make_round_step). idx pads
            # with m (out of range): `safe` clamps the GATHER so shapes
            # stay static, `mode="drop"` voids the SCATTER, and `valid`
            # zeroes the padded lanes' losses. Ready lanes past the
            # capacity are PUSHED BACK to the next event: `ready` is
            # clamped to the trained set below, so their clocks are not
            # redrawn (they stay the queue minimum) and they fire in an
            # immediately following zero-duration event.
            idx = jnp.nonzero(ready, size=cap, fill_value=m)[0]
            safe = jnp.minimum(idx, m - 1)
            valid = (idx < m).astype(jnp.float32)
            sub_args = tuple(jax.tree.map(lambda l: l[safe], a)
                             for a in train_args)
            z_sub, losses_sub = jax.vmap(train_one)(*sub_args)
            # Untrained lanes hold x exactly — the event mixer's z gate
            # discards their z anyway (they are no longer ready), so the
            # mix is bit-identical to the full-width graph's.
            z = jax.tree.map(
                lambda xl, zl: xl.at[idx].set(zl, mode="drop"),
                state.params, z_sub)
            losses = jnp.zeros((m,), jnp.float32).at[idx].set(
                losses_sub * valid, mode="drop")
            trained = jnp.zeros((m,), jnp.float32).at[idx].set(
                valid, mode="drop")
            ready = ready * trained
        else:
            z, losses = jax.vmap(train_one)(*train_args)

        if scheduled:
            W_t, active, key_q = spec.round_event(key_mix, state.round)
            ready_eff = ready * active
        else:
            W_t, key_q = W_static, key_mix
            ready_eff = ready

        version_next = state.version + ready_eff.astype(jnp.int32)
        W_eff = staleness_weights(W_t, version_next, ready_eff, async_cfg)
        x_next = ev(state.params, z, W_eff, ready_eff, key_q)

        k_dur, clock_rng = jax.random.split(state.clock_rng)
        durations = async_cfg.speed.draw(k_dur, m)
        next_ready = jnp.where(ready > 0, t_now + durations,
                               state.next_ready)

        # Loss over the clients whose clocks fired (>= 1 by construction);
        # NOT ready_eff, which can be all-zero when the only finisher is
        # schedule-inactive — 0/1 would print as a spurious perfect loss.
        lag = version_next.max() - version_next
        metrics = {
            "loss": jnp.sum(losses * ready) / ready.sum(),
            "clock": t_now,
            "ready_frac": jnp.mean(ready_eff),
            "live_edges": jnp.sum(
                (W_eff * (1.0 - jnp.eye(m, dtype=jnp.float32))) != 0.0),
            "mean_staleness": jnp.mean(lag.astype(jnp.float32)),
            "max_staleness": lag.max(),
        }
        if with_metrics or with_telemetry:
            cdist = consensus_distance(x_next)
        if with_metrics:
            metrics["consensus_dist"] = cdist
        if with_telemetry:
            with jax.named_scope("round/telemetry"):
                d = client_dim(state.params)
                fields = dict(
                    consensus_dist=cdist,
                    local_drift=consensus_distance(z),
                    live_edges=metrics["live_edges"],
                    wire_bits=wire_bits_for(d, cfg.quant,
                                            metrics["live_edges"]),
                    staleness_hist=staleness_histogram(
                        version_next, async_cfg.max_staleness),
                    dropped_edges=dropped_edge_count(
                        W_t, version_next, ready_eff,
                        async_cfg.max_staleness))
                if cfg.quant is not None and cfg.quant.enabled:
                    # The codec saw z gated to x on non-ready lanes;
                    # average the observed error over the READY lanes so
                    # busy clients' zero deltas don't dilute it.
                    z_eff = jax.tree.map(
                        lambda zl, xl: jnp.where(
                            ready_eff.reshape(
                                (-1,) + (1,) * (zl.ndim - 1)) > 0,
                            zl, xl), z, state.params)
                    # No lane sampling here: an event's readiness is
                    # sparse (often one firing client), so a strided
                    # sample would usually miss every participating lane
                    # and report zeros. ready_eff already restricts the
                    # mean to the lanes that actually published.
                    qe, qb, qs = quant_round_telemetry(
                        state.params, z_eff, cfg.quant, key_q,
                        lane_weight=ready_eff)
                    fields.update(quant_err_sq=qe, quant_bound=qb,
                                  quant_sat_frac=qs)
                metrics["telemetry"] = Telemetry(**fields)
        new_state = AsyncRoundState(
            params=x_next, rng=key_next, round=state.round + 1,
            clock=t_now, next_ready=next_ready, version=version_next,
            clock_rng=clock_rng)
        return new_state, metrics

    return event_step


def make_async_engine(loss_fn: LossFn, cfg: DFedAvgMConfig,
                      spec: MixingSpec | TopologySchedule,
                      async_cfg: AsyncConfig,
                      mesh=None, client_axes: Sequence[str] = (),
                      param_specs: Pytree | None = None,
                      fused_update=None,
                      with_metrics: bool = True,
                      with_telemetry: bool = False,
                      batch_fn: Callable | None = None) -> Callable:
    """The whole event queue in one graph: run(state, batches) scans
    :func:`make_async_round_step` over a leading EVENT axis (``batches``
    leaves [n_events, m, K, ...]) and returns (state', metrics) with every
    metric stacked [n_events]. XLA sees a single ``lax.scan`` — one
    compiled while-loop regardless of how many events are processed.

    With a version-keyed ``batch_fn`` (see :func:`make_async_round_step`)
    there is no pre-staged batch axis — call ``run(state, n_events=N)``
    and each scanned event derives its own data from the live version
    counters."""
    step = make_async_round_step(loss_fn, cfg, spec, async_cfg, mesh=mesh,
                                 client_axes=client_axes,
                                 param_specs=param_specs,
                                 fused_update=fused_update,
                                 with_metrics=with_metrics,
                                 with_telemetry=with_telemetry,
                                 batch_fn=batch_fn)

    def run(state: AsyncRoundState, batches: Pytree = None,
            n_events: int | None = None):
        if batch_fn is not None:
            if n_events is None:
                raise ValueError("version-keyed engine: pass n_events")
            return jax.lax.scan(lambda s, _: step(s, None), state, None,
                                length=n_events)
        return jax.lax.scan(step, state, batches)

    return run
