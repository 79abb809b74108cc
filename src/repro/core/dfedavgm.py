"""DFedAvgM (Algorithm 1) and quantized DFedAvgM (Algorithm 2).

One *communication round* (the jitted unit of work):

  1. every client i runs K heavy-ball SGD steps from x^t(i)   (local_sgd)
  2. unquantized: send z^t(i) = y^{t,K}(i); x^{t+1} = W z^t    (eq. 5)
     quantized:   send q^t(i) = Q(y^{t,K}(i) - x^t(i));
                  x^{t+1}(i) = x^t(i) + sum_l w_il q^t(l)      (eq. 7)

Client copies are stacked on a leading axis of size m. Local training is a
``vmap`` over that axis; gossip is a mixer from ``core.mixing``. Under pjit
the client axis is sharded over the mesh's (pod, data) axes, making each
client a tensor-parallel chip group.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from .local_sgd import local_train, local_train_deferred
from .mixing import (MixerConfig, _clients_per_shard, _quant_leaf_keys,
                     consensus_distance, make_event_mixer, make_fused_tail,
                     make_mixer)
from .quantize import QuantConfig, message_bits
from .topology import MixingSpec, TopologySchedule

Pytree = Any
LossFn = Callable[..., jnp.ndarray]

__all__ = ["DFedAvgMConfig", "RoundState", "init_round_state",
           "make_round_step", "average_params"]


@dataclasses.dataclass(frozen=True)
class DFedAvgMConfig:
    """Hyper-parameters of Algorithms 1/2.

    eta:   local learning rate (paper's eta; needs eta <= 1/(8 L K) in Thm 1)
    theta: heavy-ball momentum (paper's theta in [0, 1))
    local_steps: K — local iterations per communication round
    quant: None -> Algorithm 1; QuantConfig -> Algorithm 2
    mixer_impl: "auto" | "dense" | "ring" | "torus" | "sparse"
                (see core.mixing.MixerConfig — "sparse" executes the
                compiled GossipPlan as masked ppermutes)
    wire:  flat wire-buffer codec backend for the sparse mixer — "auto"
           (Pallas buffer kernels on TPU, XLA lowering elsewhere),
           "planar" (force the kernels), "seq" (force the XLA lowering)
    fuse_round: opt into the FUSED ROUND (``core.mixing.make_fused_tail``):
           the last two local steps fold into the wire encode/decode
           kernels and every plan step's transfer overlaps the final
           gradient. An algorithm VARIANT — it defers one local step past
           the mix (neighbors see y_{K-1}, not y_K), so it is NOT
           bit-compatible with the default round except at ``eta == 0``.
           Needs ``local_steps >= 2``; incompatible with stateful
           schedules, compute-skip gathers, and the async engine.
    """

    eta: float = 0.01
    theta: float = 0.9
    local_steps: int = 4
    quant: QuantConfig | None = None
    mixer_impl: str = "auto"
    wire: str = "auto"
    fuse_round: bool = False

    def mixer_config(self) -> MixerConfig:
        return MixerConfig(impl=self.mixer_impl, quant=self.quant,
                           wire=self.wire)


class RoundState(NamedTuple):
    """Carried state of the synchronous round loop (one jit-stable
    pytree: stacked client params, the PRNG chain, the round counter,
    and — for stateful schedules — the walk token)."""

    params: Pytree       # stacked client copies, leaves [m, ...]
    rng: jax.Array       # round-level key
    round: jnp.ndarray   # int32 counter
    # In-graph schedule state: the random-walk token position for stateful
    # random_walk schedules (None otherwise — an empty pytree leaf, so
    # checkpoints and existing callers are unaffected).
    token: jax.Array | None = None


def init_round_state(params_stacked: Pytree, key: jax.Array,
                     token: jax.Array | None = None) -> RoundState:
    """``token``: pass ``schedule.init_token()`` for a stateful
    random-walk schedule; leave None for every other topology."""
    return RoundState(params=params_stacked, rng=key,
                      round=jnp.zeros((), jnp.int32), token=token)


def _placed_boundary_lane_slots(plan, mesh, client_axes) -> float | None:
    """Wire lane slots of ``plan``'s block realization on this mesh — the
    telemetry ``placement_boundary_lanes`` constant (None when the mesh
    gives no client sharding to realize blocks on)."""
    m_local = _clients_per_shard(mesh, tuple(client_axes), plan.m)
    if m_local is None:
        return None
    return float(plan.block_plan(plan.m // m_local).num_wire_lane_slots)


def average_params(stacked: Pytree) -> Pytree:
    """Consensus/average model xbar = (1/m) sum_i x(i) (what Thm 1 tracks,
    and the model we serve)."""
    return jax.tree.map(lambda z: jnp.mean(z.astype(jnp.float32), axis=0)
                        .astype(z.dtype), stacked)


def make_round_step(loss_fn: LossFn, cfg: DFedAvgMConfig,
                    spec: MixingSpec | TopologySchedule,
                    mesh=None, client_axes: Sequence[str] = (),
                    param_specs: Pytree | None = None,
                    fused_update=None,
                    with_metrics: bool = True,
                    with_telemetry: bool = False,
                    skip_inactive_compute: bool | str = "auto",
                    async_cfg=None, placement=None) -> Callable:
    """Build round_step(state, batches) -> (state', metrics).

    ``batches``: pytree with leaves [m, K, ...] — K minibatches per client
    per round (the data pipeline shards these identically to params' client
    axis).

    ``spec`` may be a static :class:`MixingSpec` or a time-varying
    :class:`TopologySchedule`; with a schedule the round counter picks the
    mixing event W_t, inactive clients' parameters are held exactly, and
    metrics gain ``active_frac`` (the realized participation rate). A
    constant schedule is bit-identical to the static dense mixer.

    ``skip_inactive_compute``: schedules with a *statically bounded*
    active count per round (``partial(..., exact=True)`` cohorts, random
    walks: exactly 2, and i.i.d. ``partial(..., cap_slack=...)``: at most
    the cap) gather just the active lanes, run the local-SGD vmap on a
    [k, ...] stack, and scatter the results back — inactive clients'
    compute is actually SKIPPED, not computed-and-gated (k/m of the
    local-SGD FLOPs, visible in the lowered HLO). When the bound is an
    upper bound (capped i.i.d. participation) the gather is PADDED:
    unused slots index out of bounds, train a clamped dummy lane, and are
    dropped on scatter — exact whenever the round's active count fits the
    cap, which the capped schedule guarantees by construction. "auto"
    enables this whenever the count is statically bounded; True insists
    (raising if it cannot be known); False keeps the full-width vmap.
    Parameters and the ``loss`` metric are identical either way;
    ``local_drift`` is computed over the *effective* z (inactive lanes
    hold x), so with skip off it instead includes the discarded updates
    of inactive lanes.

    ``with_telemetry``: additionally emit ``metrics["telemetry"]`` — a
    :class:`repro.telemetry.Telemetry` pytree of in-graph observability
    counters (consensus distance, local drift, realized live edges and
    wire bits, quantizer error vs the Assumption-4 bound). Default OFF,
    and the off path builds the exact graph it always did (bit-identical;
    pinned by ``tests/test_telemetry.py``). The telemetry re-derives the
    round's mixing event from the same ``key_mix`` the mixer consumes, so
    it observes the realized round, never a second draw.

    ``async_cfg``: an :class:`~repro.core.async_gossip.AsyncConfig` swaps
    the synchronous barrier for the event-driven asynchronous engine —
    the returned step consumes an ``AsyncRoundState`` (see
    ``async_gossip.make_async_round_step``, which this delegates to).

    Stateful schedules (``random_walk(stateful=True)``) thread their token
    position through ``RoundState.token``: seed it with
    ``init_round_state(..., token=spec.init_token())``.

    ``placement``: a :class:`~repro.core.gossip_plan.Placement` (from
    ``compute_placement``) runs the sparse backend with lanes relabeled
    so shard boundaries follow the partition cut. Client state then
    lives in LANE order: initial params, every round's batches, the
    per-client round keys, and the schedule's active mask are gathered
    through ``placement.perm`` (lane ``p`` carries client ``perm[p]``),
    while PRNG derivation stays in client order — so placed training is
    bitwise identical to unplaced, with per-lane outputs permuted.
    Sparse impls only; incompatible with the async engine.
    """
    if placement is not None and async_cfg is not None:
        raise ValueError("placement is not supported with the async "
                         "engine (its lane bookkeeping is client-order)")
    if async_cfg is not None:
        from .async_gossip import make_async_round_step
        return make_async_round_step(
            loss_fn, cfg, spec, async_cfg, mesh=mesh,
            client_axes=client_axes, param_specs=param_specs,
            fused_update=fused_update, with_metrics=with_metrics,
            with_telemetry=with_telemetry)

    if cfg.fuse_round:
        return _make_fused_round_step(
            loss_fn, cfg, spec, mesh=mesh, client_axes=client_axes,
            param_specs=param_specs, fused_update=fused_update,
            with_metrics=with_metrics, with_telemetry=with_telemetry,
            skip_inactive_compute=skip_inactive_compute,
            placement=placement)

    scheduled = isinstance(spec, TopologySchedule)
    stateful = scheduled and spec.is_stateful
    m = spec.m

    k_active = spec.static_active_count if scheduled else None
    if skip_inactive_compute == "auto":
        skip = k_active is not None and k_active < m
    else:
        skip = bool(skip_inactive_compute)
        if skip and k_active is None:
            raise ValueError(
                "skip_inactive_compute=True needs a schedule with a "
                "statically bounded per-round active count "
                "(partial(..., exact=True), partial(..., cap_slack=...) "
                "or random_walk); got "
                f"{getattr(spec, 'name', spec)!r}")
        skip = skip and k_active < m

    perm = None if placement is None else jnp.asarray(placement.perm)
    if stateful:
        mcfg = cfg.mixer_config()
        impl = mcfg.resolved_impl(spec, mesh, client_axes)
        plan = spec.gossip_plan() if impl == "sparse" else None
        if placement is not None:
            if plan is None:
                raise ValueError("placement requires the sparse backend, "
                                 f"got impl={impl!r}")
            plan = plan.placed(placement)
        event_mixer = make_event_mixer(
            m, quant=mcfg.quant, mesh=mesh, client_axes=client_axes,
            param_specs=param_specs, plan=plan, wire=mcfg.wire, gate=True)
    else:
        mixer = make_mixer(spec, cfg.mixer_config(), mesh=mesh,
                           client_axes=client_axes, param_specs=param_specs,
                           placement=placement)

    if with_telemetry:
        # Imported lazily at BUILD time: repro.core never depends on the
        # telemetry package unless a caller opts in.
        from ..telemetry.metrics import (QUANT_SAMPLE_LANES, Telemetry,
                                         client_dim, live_edge_count,
                                         quant_round_telemetry,
                                         wire_bits_for)
        static_edges = (None if scheduled
                        else float(spec.graph.num_directed_edges()))
        # Boundary lane slots of this run's (possibly placed) block
        # realization — a compile-time constant surfaced per round so
        # placed runs are auditable next to the realized wire bill.
        placement_lanes = None
        impl_t = cfg.mixer_config().resolved_impl(spec, mesh, client_axes)
        if impl_t in ("ring", "torus", "sparse") and not (
                scheduled and spec.kind == "cycle"):
            plan_t = spec.gossip_plan()
            if placement is not None:
                plan_t = plan_t.placed(placement)
            placement_lanes = _placed_boundary_lane_slots(plan_t, mesh,
                                                          client_axes)

    def round_step(state: RoundState, batches: Pytree):
        with jax.named_scope("round/keys"):
            key_round, key_mix, key_next = jax.random.split(state.rng, 3)
            client_keys = jax.random.split(key_round, m)
            if perm is not None:
                # Lane order: lane p trains client perm[p] — its batches
                # and its round key. Keys derive in CLIENT order first
                # (single source of truth), so placed == unplaced bitwise
                # per client.
                batches = jax.tree.map(lambda b: b[perm], batches)
                client_keys = client_keys[perm]

        train_one = lambda p, b, k: local_train(
            loss_fn, p, b, k, eta=cfg.eta, theta=cfg.theta,
            fused_update=fused_update)

        # Resolve the mixing event FIRST when the active mask must gate
        # compute (stateful walks carry it; skip-compute needs it). The
        # non-stateful mixer re-derives the identical event from the same
        # key_mix, so sampling here is not a second draw.
        token_next = state.token
        active = None
        # The round's mixing event (W_t and the active mask) is the
        # mixer's per-round exchange weights, wherever it is drawn.
        with jax.named_scope("round/mix/wire/exchange"):
            if stateful:
                if state.token is None:
                    raise ValueError(
                        "stateful schedule: seed the walk with "
                        "init_round_state(..., token=spec.init_token())")
                W_t, active, key_q, token_next = spec.token_event(
                    key_mix, state.token)
            elif skip:
                # Telemetry keeps the round's W_t / key_q in hand — the
                # SAME event from the same key, not a second draw.
                if with_telemetry:
                    W_t, active, key_q = spec.round_event(key_mix,
                                                          state.round)
                else:
                    _, active, _ = spec.round_event(key_mix, state.round)
            elif scheduled and with_telemetry:
                W_t, _, key_q = spec.round_event(key_mix, state.round)
            if perm is not None and active is not None:
                # Schedule events are CLIENT-order; state is lane-order.
                active = active[perm]

        if skip:
            # Padded upper-bound gather: unused slots fill with the
            # out-of-bounds index m — their gathers clamp (training a
            # throwaway copy of the last lane) and their scatters drop,
            # so a round with fewer than k_active live clients stays
            # exact. Cohorts/walks fill every slot; capped i.i.d.
            # participation uses the slack.
            with jax.named_scope("round/local_sgd"):
                idx = jnp.nonzero(active, size=k_active, fill_value=m)[0]
                safe = jnp.minimum(idx, m - 1)
                valid = (idx < m).astype(jnp.float32)
                z_sub, losses = jax.vmap(train_one)(
                    jax.tree.map(lambda p: p[safe], state.params),
                    jax.tree.map(lambda b: b[safe], batches),
                    client_keys[safe])
                # Inactive lanes never trained: their z IS their held x.
                z = jax.tree.map(
                    lambda xl, zl: xl.at[idx].set(zl, mode="drop"),
                    state.params, z_sub)
        else:
            with jax.named_scope("round/local_sgd"):
                z, losses = jax.vmap(train_one)(state.params, batches,
                                                client_keys)

        # The round counter is passed to EVERY mixer uniformly; static
        # impls ignore it, schedules use it to pick the mixing event.
        metrics = {}
        with jax.named_scope("round/mix"):
            if stateful:
                x_next = event_mixer(state.params, z, W_t, active, key_q)
            elif scheduled:
                x_next, active = mixer(state.params, z, key_mix,
                                       state.round)
            else:
                x_next = mixer(state.params, z, key_mix, state.round)
        with jax.named_scope("round/metrics"):
            if with_metrics and scheduled:
                metrics["active_frac"] = jnp.mean(active)
            # "loss" is the mean over clients that PARTICIPATED this
            # round — inactive clients' lanes are either skipped
            # (gathered path) or discarded, so averaging them in would mix
            # in training that never entered the model. Identical whether
            # compute-skip is on or off.
            if skip:
                # Mean over the VALID slots (== the active lanes; padded
                # slots of a capped round trained a dummy and don't
                # count).
                metrics["loss"] = (jnp.sum(losses * valid)
                                   / jnp.maximum(valid.sum(), 1.0))
            elif scheduled and spec.gates_participation:
                metrics["loss"] = (jnp.sum(losses * active)
                                   / jnp.maximum(active.sum(), 1.0))
            else:
                metrics["loss"] = jnp.mean(losses)
            if with_metrics or with_telemetry:
                cdist = consensus_distance(x_next)
                drift = consensus_distance(z)
        if with_metrics:
            metrics["consensus_dist"] = cdist
            metrics["local_drift"] = drift
        if with_telemetry:
            with jax.named_scope("round/telemetry"):
                if scheduled:
                    live = live_edge_count(W_t)
                    key_q_t = key_q
                else:
                    live = jnp.float32(static_edges)
                    key_q_t = key_mix
                d = client_dim(state.params)
                fields = dict(consensus_dist=cdist, local_drift=drift,
                              live_edges=live,
                              wire_bits=wire_bits_for(d, cfg.quant, live))
                if placement_lanes is not None:
                    fields["placement_boundary_lanes"] = jnp.float32(
                        placement_lanes)
                if cfg.quant is not None and cfg.quant.enabled:
                    # The effective published z the codec saw: inactive
                    # lanes gate to x (delta 0 -> Q(0), like the mixers).
                    # err/bound average over PARTICIPATING lanes only —
                    # a zero delta hits the quantizer's s=1 zero-amax
                    # guard, which would pollute the Assumption-4 bound.
                    z_eff, lane_w = z, None
                    if scheduled and spec.gates_participation:
                        lane_w = active
                        if not skip:
                            z_eff = jax.tree.map(
                                lambda zl, xl: jnp.where(
                                    active.reshape(
                                        (-1,) + (1,) * (zl.ndim - 1)) > 0,
                                    zl, xl), z, state.params)
                    leaf_keys_t = None
                    if perm is not None and cfg.quant.stochastic:
                        # Replay in lane order: lane p uses client
                        # perm[p]'s keys, exactly like the wire.
                        leaf_keys_t = _quant_leaf_keys(
                            key_q_t, len(jax.tree.leaves(state.params)),
                            m)[:, perm]
                    qe, qb, qs = quant_round_telemetry(
                        state.params, z_eff, cfg.quant, key_q_t,
                        leaf_keys=leaf_keys_t,
                        lane_weight=lane_w,
                        sample_lanes=QUANT_SAMPLE_LANES)
                    fields.update(quant_err_sq=qe, quant_bound=qb,
                                  quant_sat_frac=qs)
                metrics["telemetry"] = Telemetry(**fields)
        with jax.named_scope("round/keys"):
            round_next = state.round + 1
        new_state = RoundState(params=x_next, rng=key_next,
                               round=round_next, token=token_next)
        return new_state, metrics

    return round_step


def _make_fused_round_step(loss_fn: LossFn, cfg: DFedAvgMConfig,
                           spec: MixingSpec | TopologySchedule,
                           mesh=None, client_axes: Sequence[str] = (),
                           param_specs: Pytree | None = None,
                           fused_update=None, with_metrics: bool = True,
                           with_telemetry: bool = False,
                           skip_inactive_compute: bool | str = "auto",
                           placement=None) -> Callable:
    """The ``cfg.fuse_round`` realization of :func:`make_round_step`: K-2
    local steps run in the usual scan (``local_train_deferred``), then the
    whole tail — penultimate update + wire encode (one fused pass), every
    plan step's ppermute, the LAST gradient inside the overlap window, and
    mix + deferred last update (one fused pass) — executes through
    ``core.mixing.make_fused_tail``. Same ``round_step(state, batches) ->
    (state', metrics)`` contract and PRNG discipline (per-step keys are
    ``jax.random.split(client_key, K)`` either way); the ``loss`` metric
    averages the identical K per-step losses. NOT bit-compatible with the
    unfused round except at ``eta == 0`` (the variant defers one step past
    the mix — see ``make_fused_tail``)."""
    scheduled = isinstance(spec, TopologySchedule)
    if scheduled and spec.is_stateful:
        raise ValueError("fuse_round does not support stateful schedules "
                         "(the walk token gates compute mid-round)")
    if skip_inactive_compute is True:
        raise ValueError("fuse_round runs the full-width client vmap; "
                         "skip_inactive_compute=True is incompatible")
    if cfg.local_steps < 2:
        raise ValueError(
            f"fuse_round needs local_steps >= 2 (one step is deferred "
            f"past the mix), got {cfg.local_steps}")
    m = spec.m
    mcfg = cfg.mixer_config()
    impl = mcfg.resolved_impl(spec, mesh, client_axes)
    # Cycle schedules switch between per-member plans in the unfused
    # sparse path; the fused tail keeps one backend per step, so they
    # take the dense reference.
    sparse = impl in ("ring", "torus", "sparse") and not (
        scheduled and spec.kind == "cycle")
    plan = spec.gossip_plan() if sparse else None
    if placement is not None:
        if plan is None:
            raise ValueError("placement requires the sparse backend, "
                             f"got impl={impl!r}")
        plan = plan.placed(placement)
    perm = None if placement is None else jnp.asarray(placement.perm)
    gate = bool(scheduled and spec.gates_participation)
    tail = make_fused_tail(
        loss_fn, m, eta=cfg.eta, theta=cfg.theta, quant=cfg.quant,
        mesh=mesh, client_axes=client_axes, param_specs=param_specs,
        plan=plan, wire=cfg.wire, gate=gate)
    ones = jnp.ones((m,), jnp.float32)
    if with_telemetry:
        from ..telemetry.metrics import (Telemetry, client_dim,
                                         live_edge_count, wire_bits_for)
        static_edges = (None if scheduled
                        else float(spec.graph.num_directed_edges()))
        placement_lanes = (None if plan is None else
                           _placed_boundary_lane_slots(plan, mesh,
                                                       client_axes))

    def round_step(state: RoundState, batches: Pytree):
        with jax.named_scope("round/keys"):
            key_round, key_mix, key_next = jax.random.split(state.rng, 3)
            client_keys = jax.random.split(key_round, m)
            if perm is not None:
                # Lane order: lane p trains client perm[p] (keys derive
                # in client order first — see make_round_step).
                batches = jax.tree.map(lambda b: b[perm], batches)
                client_keys = client_keys[perm]
        K = jax.tree.leaves(batches)[0].shape[1]

        train_head = lambda p, b, k: local_train_deferred(
            loss_fn, p, b, k, eta=cfg.eta, theta=cfg.theta,
            fused_update=fused_update)
        with jax.named_scope("round/local_sgd"):
            y, v, g, losses_head = jax.vmap(train_head)(
                state.params, batches, client_keys)      # losses [m, K-1]

        with jax.named_scope("round/mix/wire/exchange"):
            if scheduled:
                W_t, active, key_q = spec.round_event(key_mix, state.round)
                if perm is not None:
                    active = active[perm]  # client-order event, lane state
            else:
                W_t = jnp.asarray(spec.W, jnp.float32)
                active, key_q = ones, key_mix
        with jax.named_scope("round/keys"):
            batch_last = jax.tree.map(lambda b: b[:, K - 1], batches)
            keys_last = jax.vmap(
                lambda ck: jax.random.split(ck, K)[K - 1])(client_keys)

        with jax.named_scope("round/mix"):
            x_next, y_pub, loss_last = tail(
                state.params, y, v, g, batch_last, keys_last, key_q, active,
                W_t)
        metrics = {}
        with jax.named_scope("round/metrics"):
            losses = jnp.mean(
                jnp.concatenate([losses_head, loss_last[:, None]], axis=1),
                axis=1)                                  # [m], mean over K
            if scheduled and spec.gates_participation:
                metrics["loss"] = (jnp.sum(losses * active)
                                   / jnp.maximum(active.sum(), 1.0))
            else:
                metrics["loss"] = jnp.mean(losses)
            if with_metrics and scheduled:
                metrics["active_frac"] = jnp.mean(active)
            if with_metrics or with_telemetry:
                cdist = consensus_distance(x_next)
                drift = consensus_distance(y_pub)
        if with_metrics:
            metrics["consensus_dist"] = cdist
            metrics["local_drift"] = drift
        if with_telemetry:
            with jax.named_scope("round/telemetry"):
                live = (live_edge_count(W_t) if scheduled
                        else jnp.float32(static_edges))
                d = client_dim(state.params)
                # Quantizer fields stay None here: the fused tail's wire
                # delta (y1 - x, formed INSIDE the encode kernels) never
                # exists as a separate tensor to replay against.
                metrics["telemetry"] = Telemetry(
                    consensus_dist=cdist, local_drift=drift,
                    live_edges=live,
                    wire_bits=wire_bits_for(d, cfg.quant, live),
                    placement_boundary_lanes=(
                        None if placement_lanes is None
                        else jnp.float32(placement_lanes)))
        with jax.named_scope("round/keys"):
            round_next = state.round + 1
        new_state = RoundState(params=x_next, rng=key_next,
                               round=round_next, token=state.token)
        return new_state, metrics

    return round_step


def round_comm_bits(spec: MixingSpec | TopologySchedule, n_params: int,
                    quant: QuantConfig | None,
                    t: int | None = None, plan=None) -> float:
    """Bits moved on the graph in ONE round (paper §3.2 accounting): every
    *participating* client sends its (possibly quantized) message across
    each *live* directed edge.

    Static spec: exact integer count. TopologySchedule: the expectation
    over the round's sampled edge set (exact for deterministic kinds —
    constant / cycle / random_walk — pass ``t`` to resolve a specific
    round of a cycle). The bill is the SAME for both mixer backends —
    dense and sparse realize the identical algorithmic exchange, so
    ``plan`` is accepted for call-site compatibility but no longer
    switches to realized-plan-edge billing (that wire-level diagnostic is
    :func:`repro.core.comm_cost.plan_round_bits`)."""
    del plan
    if isinstance(spec, TopologySchedule):
        from .comm_cost import schedule_round_bits
        return schedule_round_bits(spec, n_params, quant, t)
    qc = quant if quant is not None else QuantConfig(bits=32)
    return message_bits(n_params, qc) * spec.graph.num_directed_edges()
