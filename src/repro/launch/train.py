"""End-to-end DFedAvgM training driver (deliverable (b)'s e2e example uses
this; also usable standalone):

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --rounds 50 --clients 8 --bits 8

By default it trains the config's reduced CPU smoke-test variant on
synthetic LM data; ``--no-reduced`` keeps the published widths and
``--layers N`` cuts only the depth (``chip_smoke.py`` runs OLMo-1B that
way on one TPU).
"""
from __future__ import annotations

import argparse
import dataclasses
import time
import warnings
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config, reduced as make_reduced
from ..core import (AsyncConfig, CommLedger, DFedAvgMConfig, MixingSpec,
                    QuantConfig, SpeedModel, TopologySchedule,
                    async_event_bits, average_params, init_async_state,
                    init_round_state, make_round_step, round_comm_bits)
from ..core.topology import erdos_renyi_graph, ring_graph, torus_graph
from ..data.synthetic import lm_client_batches, lm_round_batches
from ..models import model as M
from ..telemetry import RunLog, Tracer, telemetry_host


class RunResult(NamedTuple):
    """What a resident run returns. ``step`` is the jitted round step and
    ``batches`` one round's input, so ``step.lower(state, batches)``
    shows the round program that ran; ``rounds`` are the round records
    the run logged (``t``, ``loss``, ``round_s``, ...)."""

    state: Any
    metrics: dict
    step: Callable
    batches: Any
    rounds: list


# RunLog round-record fields pulled straight out of the step's metrics
# dict when present (the telemetry pytree, if any, is merged first and
# wins — it is the realized, cross-checkable value).
_METRIC_FIELDS = ("consensus_dist", "active_frac", "clock", "ready_frac",
                  "mean_staleness", "max_staleness", "live_edges")


def _round_fields(metrics, comm_bits=None):
    """metrics dict (jit output or pooled-runner host dict) -> plain
    python kwargs for ``RunLog.round``. One host transfer for the
    telemetry pytree; scalar metrics are pulled individually only when
    the record is actually being written."""
    out = {}
    tel = metrics.get("telemetry")
    if tel is not None:
        out.update(telemetry_host(tel))
    for k in _METRIC_FIELDS:
        if k in metrics and k not in out:
            out[k] = float(metrics[k])
    for k, v in metrics.items():
        if k.startswith("pool_") or k == "cohort_size":
            out[k] = float(v) if not isinstance(v, (list, int)) else v
    if "staleness_hist" in metrics and "staleness_hist" not in out:
        out["staleness_hist"] = [int(c) for c in metrics["staleness_hist"]]
    if "wire_bits" in metrics and "wire_bits" not in out:
        out["wire_bits"] = float(metrics["wire_bits"])
    if comm_bits is not None:
        out["comm_bits"] = float(comm_bits)
    return out


def _placement(mesh, spec: jax.sharding.PartitionSpec
               ) -> jax.sharding.NamedSharding:
    """``spec`` on ``mesh`` in the form the jitted step gives its outputs:
    no mesh axes of size 1, no trailing ``None``. A spec that differs
    from the outputs' only in form is the same layout, but it misses
    jit's fast dispatch cache, and round 2 would take the slow path."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    parts = []
    for e in spec:
        names = tuple(a for a in ((e,) if isinstance(e, str) else e or ())
                      if sizes[a] > 1)
        parts.append(names[0] if len(names) == 1 else (names or None))
    while parts and parts[-1] is None:
        parts.pop()
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(*parts))


def build_topology(args, m: int):
    """CLI -> static MixingSpec or time-varying TopologySchedule."""
    ring = MixingSpec.ring(m, self_weight=args.self_weight)
    if args.schedule == "static":
        return ring
    if args.schedule == "constant":
        return TopologySchedule.constant(ring)
    if args.schedule == "edge-sample":
        base = (erdos_renyi_graph(m, args.er_p, seed=args.seed)
                if args.base_graph == "er" else ring_graph(m))
        return TopologySchedule.edge_sample(base, args.edge_p)
    if args.schedule == "partial":
        base = (erdos_renyi_graph(m, args.er_p, seed=args.seed)
                if args.base_graph == "er" else ring_graph(m))
        return TopologySchedule.partial(base, args.p_active,
                                        exact=args.exact_partial,
                                        cap_slack=args.partial_cap_slack)
    if args.schedule == "random-walk":
        base = (erdos_renyi_graph(m, args.er_p, seed=args.seed)
                if args.base_graph == "er" else ring_graph(m))
        return TopologySchedule.random_walk(base, horizon=max(args.rounds, 64),
                                            seed=args.seed,
                                            stateful=args.stateful_walk)
    if args.schedule == "cycle":
        rows = next((r for r in range(int(m ** 0.5), 1, -1) if m % r == 0),
                    None)
        if rows is None:
            raise SystemExit(f"--schedule cycle needs composite m, got {m}")
        return TopologySchedule.cycle(
            [ring, MixingSpec.torus(rows, m // rows)])
    raise SystemExit(f"unknown --schedule {args.schedule!r}")


def run_pooled(args, cfg, log, tracer):
    """Virtual-client-pool execution: all ``--clients`` live in a host-
    side :class:`~repro.core.client_pool.ClientPool`; only the round's
    cohort (``--resident-lanes`` wide) is materialized on device. Scales
    m to 10^5-10^6 on one host — the structural-ring schedule
    constructors never build the O(m^2) adjacency, and data is generated
    per cohort, keyed on (client id, progress counter). With
    ``--telemetry`` the pooled path reports ``consensus_dist`` over the
    FULL pool (host-side, f64 accumulation) like the resident path."""
    from ..core import (ClientPool, PoolSchedule, PooledAsyncRunner,
                        PooledRunner)
    from .mesh import resident_lane_capacity

    m = args.clients
    quant = QuantConfig(bits=args.bits) if args.bits < 32 else None
    dfed = DFedAvgMConfig(eta=args.eta, theta=args.theta,
                          local_steps=args.local_steps, quant=quant)
    key = jax.random.PRNGKey(args.seed)
    k_init, k_state, k_data = jax.random.split(key, 3)
    params, _ = M.init_model(k_init, cfg)
    template = params
    d = cfg.n_params()

    lanes = args.resident_lanes
    if lanes is None:
        per_client = sum(np.dtype(l.dtype).itemsize * l.size
                         for l in jax.tree.leaves(template))
        lanes = min(m, resident_lane_capacity(per_client))
    loss = lambda p, b, r: M.loss_fn(p, cfg, b, r)
    pool = ClientPool(template, m)
    data_kw = dict(K=args.local_steps, batch=args.batch, seq=args.seq,
                   vocab=cfg.vocab_size)

    if args.async_gossip:
        speed = {"constant": SpeedModel.constant(),
                 "lognormal": SpeedModel.lognormal(),
                 "straggler": SpeedModel.straggler()}[args.speed_model]
        acfg = AsyncConfig(speed=speed, max_staleness=args.max_staleness,
                           eta_staleness_decay=args.eta_staleness_decay)
        bf = lambda ids, vers: lm_client_batches(k_data, ids, vers,
                                                 **data_kw)
        runner = PooledAsyncRunner(pool, loss, dfed, acfg, bf,
                                   key=k_state, capacity=lanes,
                                   ring_self_weight=args.self_weight,
                                   telemetry=args.telemetry, tracer=tracer)
        log.info(f"pooled async: m={m} capacity={lanes} "
                 f"speed={args.speed_model} (rounds are EVENTS)")
    else:
        if args.schedule == "random-walk":
            psched = PoolSchedule.ring_random_walk(
                m, horizon=max(args.rounds, 64), seed=args.seed)
        elif args.schedule == "partial" and args.base_graph == "er":
            # small-m only: dense base retained via the resident schedule
            psched = PoolSchedule.from_schedule(build_topology(args, m))
        else:
            psched = PoolSchedule.ring_partial(m, lanes / m)
        backend = "sparse" if args.mixer_impl == "sparse" else "dense"
        # sync cohorts are globally ordered, so (client, round) keying is
        # deterministic and prefetch-safe
        bf = lambda idx, t: lm_client_batches(
            k_data, idx, np.full(idx.shape, t, np.int32), **data_kw)
        runner = PooledRunner(pool, psched, loss, dfed, bf, key=k_state,
                              backend=backend, telemetry=args.telemetry,
                              tracer=tracer)
        log.info(f"pooled: m={m} schedule={psched.name} "
                 f"cohort={psched.cohort_size} backend={backend} "
                 f"(E[edges/round]={psched.expected_directed_edges():.1f})")

    metrics = {}
    async_bits = 0.0
    for t in range(args.rounds):
        metrics = (runner.step_event() if args.async_gossip
                   else runner.round())
        if args.async_gossip:
            async_bits += async_event_bits(
                d, quant, live_edges=float(metrics["live_edges"]))
        if args.ckpt_dir and not args.async_gossip \
                and (t + 1) % args.ckpt_every == 0:
            with tracer.span("round/checkpoint", t=t):
                runner.save(args.ckpt_dir)
        cadence = t % max(1, args.rounds // 10) == 0 or t == args.rounds - 1
        if log.jsonl is not None or cadence:
            bits = async_bits if args.async_gossip else runner.comm_bits
            fields = _round_fields(metrics, comm_bits=bits)
            fields.setdefault("pool_materialized", int(pool.materialized))
            fields.setdefault("pool_mbytes", pool.nbytes / 2**20)
            log.round(t, float(metrics["loss"]), console=cadence, **fields)
    log.info(f"done; {pool.materialized} of {m} clients materialized, "
             f"{pool.nbytes/2**20:.1f}MB host params")
    bits = async_bits if args.async_gossip else runner.comm_bits
    log.end(args.rounds, comm_bits=float(bits),
            final_loss=float(metrics["loss"]) if metrics else None)
    return runner, metrics


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="train the config's CPU smoke-test variant "
                         "(configs.reduced: d_model<=256, vocab<=512, "
                         "float32); --no-reduced keeps the published "
                         "widths and dtype")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to N layers; no width changes")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--eta", type=float, default=3e-2)
    ap.add_argument("--theta", type=float, default=0.9)
    ap.add_argument("--bits", type=int, default=32)
    ap.add_argument("--mixer-impl", default="auto",
                    choices=["auto", "dense", "sparse"],
                    help="gossip backend: dense einsum vs sparse GossipPlan"
                         " ppermutes; auto picks sparse when this host has"
                         " >= one device per client BLOCK (see"
                         " --clients-per-shard)")
    ap.add_argument("--clients-per-shard", type=int, default=1,
                    help="clients per device shard for the sparse backend "
                         "(must divide --clients). >1 block-shards the "
                         "client axis so m scales past the device count: "
                         "intra-block gossip edges are on-device gathers, "
                         "only boundary lanes touch the wire")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="model-parallel degree of the 2D (clients, "
                         "model) mesh: params shard over the model axis "
                         "(sharding.rules strategy A) and each of the "
                         "model_parallel device columns ships only its "
                         "1/model_parallel slice of every boundary "
                         "gossip lane, so per-device wire drops "
                         "~linearly with the degree; needs n_shards * "
                         "model_parallel devices and the sparse backend "
                         "(incompatible with --fuse-round and --pool)")
    ap.add_argument("--placement", default="contiguous",
                    choices=["contiguous", "partition"],
                    help="client -> lane placement for the sparse backend: "
                         "contiguous keeps client c on shard "
                         "c // clients_per_shard (optimal for rings/tori); "
                         "partition runs the compile-time graph-partition "
                         "pass (greedy block growth + Kernighan-Lin "
                         "refinement) on the support graph to minimize "
                         "boundary wire lanes on irregular graphs — "
                         "training is bitwise identical, only the lane "
                         "layout (and the wire bytes) change")
    ap.add_argument("--wire", default="auto",
                    choices=["auto", "seq", "planar"],
                    help="flat wire-buffer codec for the sparse mixer: "
                         "planar = Pallas buffer kernels (TPU), seq = the "
                         "XLA lowering of the same math (CPU); auto picks "
                         "by backend")
    ap.add_argument("--self-weight", type=float, default=0.5,
                    help="ring self weight (0.5 => PSD W, safe for Alg. 2)")
    ap.add_argument("--fuse-round", action="store_true",
                    help="fused overlapped round variant: the last local "
                         "step is folded into the wire encode, the final "
                         "gradient computes inside the gossip window, and "
                         "mix + momentum apply in one decode pass (needs "
                         "--local-steps >= 2; a different algorithm "
                         "variant, not bit-identical to the default)")
    ap.add_argument("--schedule", default="static",
                    choices=["static", "constant", "edge-sample", "partial",
                             "random-walk", "cycle"],
                    help="time-varying topology schedule (static = old path)")
    ap.add_argument("--base-graph", default="ring", choices=["ring", "er"],
                    help="base graph for sampled schedules")
    ap.add_argument("--edge-p", type=float, default=0.7,
                    help="per-round edge keep probability (edge-sample)")
    ap.add_argument("--p-active", type=float, default=0.7,
                    help="per-round client participation prob (partial)")
    ap.add_argument("--er-p", type=float, default=0.5,
                    help="ER base-graph edge density (--base-graph er)")
    ap.add_argument("--exact-partial", action="store_true",
                    help="partial schedule draws an EXACT cohort of "
                         "round(p_active*m) clients; the static count lets "
                         "the round step skip inactive clients' compute")
    ap.add_argument("--partial-cap-slack", type=int, default=None,
                    help="cap i.i.d. partial participation at "
                         "ceil(p_active*m)+slack clients per round — a "
                         "static upper bound that buys the same local-SGD "
                         "compute skip via a padded gather")
    ap.add_argument("--stateful-walk", action="store_true",
                    help="random-walk token as in-graph RoundState instead "
                         "of a precomputed host-side path")
    ap.add_argument("--async-gossip", action="store_true",
                    help="drop the round barrier: event-driven async "
                         "engine with staleness-aware mixing")
    ap.add_argument("--speed-model", default="lognormal",
                    choices=["constant", "lognormal", "straggler"],
                    help="per-client compute-duration distribution "
                         "(--async-gossip)")
    ap.add_argument("--max-staleness", type=int, default=8,
                    help="neighbors staler than this many local rounds "
                         "get mixing weight 0 (--async-gossip)")
    ap.add_argument("--eta-staleness-decay", type=float, default=0.0,
                    help="staleness-adaptive local LR (--async-gossip): "
                         "a client lagging s local rounds trains with "
                         "eta/(1+decay*s); 0 disables")
    ap.add_argument("--pool", action="store_true",
                    help="virtual client pool: hold all --clients in a "
                         "host-side COW parameter store and materialize "
                         "only the round's cohort as device lanes — "
                         "scales m to 1e5-1e6 on one host (ring base; "
                         "schedules: partial, random-walk, or "
                         "--async-gossip)")
    ap.add_argument("--resident-lanes", type=int, default=None,
                    help="device lanes for pooled execution (sync: the "
                         "cohort size; async: the ready-set capacity); "
                         "default sizes it from device memory via "
                         "mesh.resident_lane_capacity")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None,
                    help="save RoundState every --ckpt-every rounds")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--telemetry", action="store_true",
                    help="build the round step with in-graph telemetry "
                         "(consensus distance, realized wire bits, "
                         "quantizer error vs the Assumption-4 bound, ...) "
                         "— the off path is bit-identical to not passing "
                         "this flag")
    ap.add_argument("--log-jsonl", default=None, metavar="PATH",
                    help="write EVERY round as a schema-validated JSONL "
                         "record (the console keeps its sparse cadence; "
                         "see docs/OBSERVABILITY.md)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write host-stage spans as Chrome trace-event "
                         "JSON, viewable in Perfetto (ui.perfetto.dev)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)
    if args.layers is not None:
        if args.layers < 1:
            raise SystemExit(f"--layers {args.layers} must be >= 1")
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    cfg = dataclasses.replace(cfg, remat=False)
    from .compile_cache import use_compile_cache
    use_compile_cache()
    log = RunLog(jsonl=args.log_jsonl)
    tracer = Tracer(enabled=args.trace is not None)
    log.start(config={k: v for k, v in vars(args).items()})
    try:
        if args.pool:
            if args.model_parallel > 1:
                raise SystemExit(
                    "--model-parallel > 1 is incompatible with --pool "
                    "(pooled lanes hold full replicas in the host store; "
                    "the 2D mesh is a resident-execution layout)")
            # Branches BEFORE build_topology: pooled schedules on a ring
            # base are constructed structurally, so no O(m^2) adjacency
            # exists at m = 1e5-1e6.
            if args.placement == "partition":
                raise SystemExit(
                    "--placement partition is incompatible with --pool "
                    "(pooled lanes are cohort slots, not fixed clients, "
                    "and no O(m^2) support adjacency exists)")
            return run_pooled(args, cfg, log, tracer)
        return _run_resident(args, cfg, log, tracer)
    finally:
        if args.trace:
            tracer.save(args.trace)
        log.close()


def _run_resident(args, cfg, log, tracer):
    m = args.clients

    quant = QuantConfig(bits=args.bits) if args.bits < 32 else None
    spec = build_topology(args, m)

    # Backend selection: sparse needs a mesh with one client BLOCK per
    # shard (clients_per_shard=1 is the classic one-client-per-device
    # layout; >1 lets m exceed the device count).
    if args.model_parallel < 1:
        raise SystemExit(f"--model-parallel {args.model_parallel} "
                         f"must be >= 1")
    if args.model_parallel > 1 and args.mixer_impl == "dense":
        raise SystemExit("--model-parallel > 1 needs the sparse backend "
                         "(the dense einsum reference mixes full "
                         "replicas); drop --mixer-impl dense")
    if args.model_parallel > 1 and args.fuse_round:
        raise SystemExit(
            "--fuse-round is incompatible with --model-parallel > 1: the "
            "fused tail computes the last gradient inside the client "
            "shard_map body, which would only see a 1/model_parallel "
            "slice of the params; run the unfused round (its local SGD "
            "auto-partitions over the model axis under GSPMD)")
    from .mesh import make_client_mesh
    mesh = client_axes = None
    if args.mixer_impl in ("auto", "sparse"):
        if args.clients_per_shard < 1 or m % args.clients_per_shard:
            raise SystemExit(f"--clients-per-shard {args.clients_per_shard} "
                             f"must be >= 1 and divide --clients {m}")
        mesh = make_client_mesh(m,
                                clients_per_shard=args.clients_per_shard,
                                model_parallel=args.model_parallel)
        if mesh is None and (args.mixer_impl == "sparse"
                             or args.model_parallel > 1):
            need = (m // args.clients_per_shard) * args.model_parallel
            raise SystemExit(
                f"this run needs >= {need} devices "
                f"({m // args.clients_per_shard} client shards x "
                f"{args.model_parallel} model columns), this host has "
                f"{jax.device_count()}; raise --clients-per-shard or "
                f"lower --model-parallel to fit")
    impl = "sparse" if mesh is not None else "dense"
    client_axes = ("clients",) if mesh is not None else ()
    # Where the host has a device per client shard, the dense reference's
    # clients live on the client mesh a sparse run would use: its einsum
    # then gathers over that mesh, and no device holds every client's
    # local SGD.
    place_mesh = mesh
    cps = args.clients_per_shard
    if (args.mixer_impl == "dense" and not args.async_gossip
            and cps >= 1 and m % cps == 0
            and 1 < m // cps <= jax.device_count()):
        place_mesh = make_client_mesh(
            m, clients_per_shard=args.clients_per_shard)
    dfed = DFedAvgMConfig(eta=args.eta, theta=args.theta,
                          local_steps=args.local_steps, quant=quant,
                          mixer_impl=impl, wire=args.wire,
                          fuse_round=args.fuse_round)
    scheduled = isinstance(spec, TopologySchedule)
    placement = None
    if args.placement == "partition":
        if impl != "sparse":
            raise SystemExit(
                "--placement partition needs the sparse backend (this run "
                "resolved to the dense reference); see --mixer-impl / "
                "--clients-per-shard")
        if args.async_gossip:
            raise SystemExit("--placement partition is incompatible with "
                             "--async-gossip (client-order lane "
                             "bookkeeping)")
        from ..core.gossip_plan import compute_placement
        support = spec.support_graph() if scheduled else spec.graph
        placement = compute_placement(support,
                                      m // args.clients_per_shard)
        cut0 = support.block_boundary_edges(args.clients_per_shard)
        cut1 = support.block_boundary_edges(args.clients_per_shard,
                                            perm=placement)
        log.info(f"placement: partition over "
                 f"{m // args.clients_per_shard} shards — directed "
                 f"boundary edges {cut0} (contiguous) -> {cut1} (placed)")
    plan = None
    if impl == "sparse":
        # A cycle compiles one plan per member (lax.switch at run time);
        # everything else one union-support plan.
        plans = spec.gossip_plans() if scheduled else [spec.gossip_plan()]
        if placement is not None:
            plans = [p.placed(placement) for p in plans]
        plan = plans if len(plans) > 1 else plans[0]
    if scheduled:
        log.info(f"topology schedule: {spec.name} "
                 f"(E[directed edges/round] = "
                 f"{spec.expected_directed_edges():.1f})")
    if plan is not None:
        for p in (plan if isinstance(plan, list) else [plan]):
            if args.clients_per_shard > 1:
                bp = p.block_plan(m // args.clients_per_shard)
                log.info(f"mixer backend: sparse ({p.name}: "
                         f"{args.clients_per_shard} clients/shard over "
                         f"{bp.n_shards} shards, {bp.num_collectives} "
                         f"ppermutes, {bp.num_wire_lane_slots} boundary "
                         f"wire lanes per round)")
            else:
                log.info(f"mixer backend: sparse ({p.name}: {p.n_steps} "
                         f"ppermute steps, {p.num_directed_wire_edges} "
                         f"realized wire edges per round)")
    else:
        log.info("mixer backend: dense (einsum reference)")

    key = jax.random.PRNGKey(args.seed)
    k_init, k_state, k_data = jax.random.split(key, 3)
    params, axes = M.init_model(k_init, cfg)
    stacked = jax.tree.map(
        lambda t: jnp.broadcast_to(t[None], (m,) + t.shape), params)
    param_specs = None
    if args.model_parallel > 1:
        # 2D (clients, model) mesh: shard each leaf's inner dims over the
        # model axis (strategy-A rules; leaves whose dims don't divide
        # stay replicated) and lay the stacked params out that way up
        # front so the round step never gathers a full replica per lane.
        from ..sharding.rules import RULES_A, specs_for_tree
        param_specs = specs_for_tree(axes, stacked, RULES_A, mesh,
                                     leading_client=("clients",))
        stacked = jax.device_put(
            stacked,
            jax.tree.map(lambda s: _placement(mesh, s), param_specs,
                         is_leaf=lambda s: isinstance(
                             s, jax.sharding.PartitionSpec)))
        n_sharded = sum(
            any(e is not None and "model" in (e if isinstance(e, tuple)
                                              else (e,))
                for e in s)
            for s in jax.tree.leaves(
                param_specs,
                is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec)))
        n_leaves = len(jax.tree.leaves(stacked))
        log.info(f"2D mesh: model_parallel={args.model_parallel}, "
                 f"{n_sharded}/{n_leaves} param leaves model-sharded "
                 f"(the rest replicate per column)")
    elif place_mesh is not None:
        # Client blocks go to their shards up front, so no device holds
        # every client's copy on the way into the first round.
        clients_on_mesh = _placement(place_mesh,
                                     jax.sharding.PartitionSpec("clients"))
        stacked = jax.device_put(stacked, clients_on_mesh)

    loss = lambda p, b, r: M.loss_fn(p, cfg, b, r)
    acfg = None
    if args.async_gossip:
        speed = {"constant": SpeedModel.constant(),
                 "lognormal": SpeedModel.lognormal(),
                 "straggler": SpeedModel.straggler()}[args.speed_model]
        acfg = AsyncConfig(speed=speed, max_staleness=args.max_staleness,
                           eta_staleness_decay=args.eta_staleness_decay)
        log.info(f"async gossip: speed={args.speed_model} "
                 f"max_staleness={args.max_staleness} "
                 f"eta_staleness_decay={args.eta_staleness_decay} "
                 f"(rounds are EVENTS)")
    # Donating the round state lets XLA reuse the params/momentum HBM in
    # place instead of round-tripping a fresh copy every round (a no-op
    # warning on CPU, a real saving on device).
    warnings.filterwarnings("ignore",
                            message="Some donated buffers were not usable")
    round_step = make_round_step(loss, dfed, spec, mesh=mesh,
                                 client_axes=client_axes or (),
                                 param_specs=param_specs,
                                 async_cfg=acfg,
                                 with_telemetry=args.telemetry,
                                 placement=placement)
    if mesh is None and place_mesh is not None:
        # GSPMD would hand the dense mix back replicated on every device;
        # the clients stay on their shards for the next round.
        def round_step(state, batches, _step=round_step):
            state, metrics = _step(state, batches)
            return state._replace(params=jax.lax.with_sharding_constraint(
                state.params, clients_on_mesh)), metrics
    step = jax.jit(round_step, donate_argnums=(0,))
    if acfg is not None:
        state = init_async_state(stacked, k_state, acfg.speed)
    else:
        token = (spec.init_token()
                 if scheduled and spec.is_stateful else None)
        state = init_round_state(stacked, k_state, token=token)
    if place_mesh is not None:
        # The step returns its scalars (round counter, PRNG key) on the
        # mesh; committing them there up front keeps round 2 from
        # compiling the step a second time.
        rep = jax.sharding.NamedSharding(place_mesh,
                                         jax.sharding.PartitionSpec())
        state = jax.tree.map(
            lambda a: a if isinstance(a.sharding, jax.sharding.NamedSharding)
            else jax.device_put(a, rep), state)

    d = cfg.n_params()
    if plan is not None and args.model_parallel > 1:
        from ..core.comm_cost import plan_round_bits
        wire_1d = plan_round_bits(plan, d, quant,
                                  clients_per_shard=args.clients_per_shard,
                                  placement=placement)
        wire_col = plan_round_bits(plan, d, quant,
                                   clients_per_shard=args.clients_per_shard,
                                   placement=placement,
                                   model_parallel=args.model_parallel)
        log.info(f"per-device wire: {wire_col / 8 / 1e6:.2f} MB/round "
                 f"per model column (1D bill {wire_1d / 8 / 1e6:.2f} MB, "
                 f"{wire_1d / max(wire_col, 1e-9):.1f}x reduction)")
    # One billing convention for both backends: the live-directed-edge
    # expectation (paper §3.2). Async: realized live edges are billed per
    # event below (the set varies with readiness and staleness).
    ledger = CommLedger(0.0 if acfg is not None
                        else round_comm_bits(spec, d, quant))
    records = []
    for t in range(args.rounds):
        t_round = time.perf_counter()
        with tracer.span("round/data", t=t):
            if acfg is not None:
                # Async events are unordered across clients, so data must
                # key on each client's OWN progress counter — a global
                # round index would feed a client different batches
                # whenever the fleet's interleaving changed (see
                # data.lm_client_batches).
                batches = lm_client_batches(
                    k_data, jnp.arange(m), state.version,
                    K=args.local_steps, batch=args.batch, seq=args.seq,
                    vocab=cfg.vocab_size)
            else:
                batches = lm_round_batches(k_data, t, m=m,
                                           K=args.local_steps,
                                           batch=args.batch, seq=args.seq,
                                           vocab=cfg.vocab_size)
        with tracer.span("round/step", t=t):
            state, metrics = step(state, batches)
            if tracer.enabled:
                # Fold device time into the span; untraced runs keep the
                # async-dispatch overlap untouched.
                jax.block_until_ready(metrics["loss"])
        if acfg is not None:
            ledger.add_bits(async_event_bits(
                d, quant, live_edges=float(metrics["live_edges"])))
        else:
            ledger.tick()
        if args.ckpt_dir and (t + 1) % args.ckpt_every == 0:
            from ..checkpoint import save_checkpoint
            with tracer.span("round/checkpoint", t=t):
                save_checkpoint(args.ckpt_dir, t + 1, state)
        cadence = t % max(1, args.rounds // 10) == 0 or t == args.rounds - 1
        if log.jsonl is not None or cadence:
            with tracer.span("round/d2h", t=t):
                fields = _round_fields(metrics,
                                       comm_bits=ledger.total_bits)
                if acfg is not None:
                    fields.setdefault("clock", float(state.clock))
                loss_t = float(metrics["loss"])
                records.append(log.round(
                    t, loss_t, console=cadence,
                    round_s=time.perf_counter() - t_round, **fields))
    avg = average_params(state.params)
    log.info(f"done; consensus model leaves: {len(jax.tree.leaves(avg))}")
    log.end(args.rounds, comm_bits=float(ledger.total_bits),
            final_loss=float(metrics["loss"]),
            final_consensus_dist=(float(metrics["consensus_dist"])
                                  if "consensus_dist" in metrics else None))
    return RunResult(state, metrics, step, batches, records)


if __name__ == "__main__":
    main()
