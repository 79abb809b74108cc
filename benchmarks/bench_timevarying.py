"""Time-varying gossip: static ring vs sampled / partial / random-walk
schedules (beyond-paper; cf. random-walk DFedAvg arXiv:2508.21286 and
FedPAQ arXiv:1909.13014 partial participation).

For each schedule we train the paper's 2NN on the synthetic classification
task and report wall time per round plus the headline trade-off: consensus
distance reached vs (expected) bits moved per round. Run standalone:

  PYTHONPATH=src python benchmarks/bench_timevarying.py --smoke

The dense-vs-sparse backend comparison (HLO collective bytes + wall clock
on an 8-device host mesh, written to BENCH_gossip.json at the repo root)
runs in a subprocess so this process keeps its single CPU device.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import textwrap

from repro.core import (MixingSpec, QuantConfig, TopologySchedule,
                        schedule_round_bits)
from repro.core.comm_cost import dfedavgm_round_bits
from repro.core.topology import erdos_renyi_graph, ring_graph

REPO = pathlib.Path(__file__).resolve().parent.parent
GOSSIP_JSON = REPO / "BENCH_gossip.json"

try:
    from .common import train_dfedavgm_2nn
except ImportError:  # standalone: python benchmarks/bench_timevarying.py
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    from benchmarks.common import train_dfedavgm_2nn


def schedules(m: int, rounds: int, seed: int = 0):
    ring = MixingSpec.ring(m, self_weight=0.5)
    er = erdos_renyi_graph(m, 0.4, seed=seed)
    return [
        ("static_ring", ring),
        ("constant_sched", TopologySchedule.constant(ring)),
        ("er_edge_sample", TopologySchedule.edge_sample(er, p_edge=0.5)),
        ("ring_partial", TopologySchedule.partial(ring_graph(m),
                                                  p_active=0.6)),
        ("ring_random_walk", TopologySchedule.random_walk(
            ring_graph(m), horizon=max(rounds, 64), seed=seed)),
    ]


# ---------------------------------------------------------------------------
# Dense vs sparse backend: HLO collective bytes + wall clock per round
# ---------------------------------------------------------------------------

def _run_json_subprocess(src: str, devices: int) -> dict:
    """Run a bench source template in a subprocess with ``devices`` fake
    host devices and parse its ``JSON::`` payload — the one runner both
    compare arms share, so env setup and result protocol can't drift
    between them."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") +
        f" --xla_force_host_platform_device_count={devices}").strip()
    # src for repro, the repo root for benchmarks.common.timeit_best —
    # the subprocess arms time themselves with the same primitive as the
    # in-process benches.
    env["PYTHONPATH"] = os.pathsep.join([str(REPO / "src"), str(REPO)])
    # The parent has already run JAX and may hold the accelerator; these
    # arms are fake-host-device CPU runs by construction.
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(src)],
                       capture_output=True, text=True, timeout=900, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"gossip compare subprocess failed:\n{r.stderr}")
    payload = next(l for l in r.stdout.splitlines()
                   if l.startswith("JSON::"))[len("JSON::"):]
    return json.loads(payload)


_COMPARE_SRC = """
    import json, warnings
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from benchmarks.common import timeit_best
    from repro.core import (MixerConfig, QuantConfig, TopologySchedule,
                            make_mixer, plan_round_bits,
                            schedule_round_bits)
    from repro.core.topology import ring_graph
    from repro.launch.hlo_stats import collect_collectives

    warnings.filterwarnings("ignore",
                            message="Some donated buffers were not usable")
    m, d, iters = {m}, {d}, {iters}
    mesh = Mesh(np.array(jax.devices()[:m]), ("clients",))
    sched = TopologySchedule.edge_sample(ring_graph(m), p_edge=0.5)
    plan = sched.gossip_plan()
    sh = NamedSharding(mesh, P("clients", None))
    x_host = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (m, d)))
    z = jax.device_put(jax.random.normal(jax.random.PRNGKey(1), (m, d)), sh)
    out = {{"m": m, "d": d, "schedule": sched.name,
            "plan_steps": plan.n_steps,
            "plan_wire_edges": plan.num_directed_wire_edges}}
    for bits in (32, 8):
        q = (QuantConfig(bits=bits, stochastic=False, delta_mode="eq7")
             if bits < 32 else None)
        for impl in ("dense", "sparse"):
            mx = make_mixer(sched, MixerConfig(impl=impl, quant=q),
                            mesh=mesh if impl == "sparse" else None,
                            client_axes=("clients",))
            # Donating x lets the round update reuse the params buffer in
            # place (the flat wire path's HBM saving on device; a no-op
            # on CPU hosts).
            fn = jax.jit(lambda a, b, k, t: mx({{"w": a}}, {{"w": b}},
                                               k, t)[0]["w"],
                         donate_argnums=(0,))
            key = jax.random.PRNGKey(2)
            x = jax.device_put(x_host, sh)   # fresh per arm (donated below)
            txt = fn.lower(x, z, key, 0).compile().as_text()
            stats = collect_collectives(txt).as_dict()
            r = jax.block_until_ready(fn(x, z, key, 0))   # warmup/compile
            # Best-of-3 timing reps: the CI perf gate compares arms, and a
            # single scheduler hiccup on the shared runner must not flip it.
            us, r = timeit_best(lambda t, r: fn(r, z, key, t), r,
                                iters=iters, reps=3)
            arm = {{
                "wire_bytes_per_device": stats["wire_bytes"],
                "collectives": stats["counts"],
                "us_per_round": us,
                # One billing convention for both backends (live-edge
                # expectation); the sparse arm also reports the wire
                # DIAGNOSTIC (full masked plan schedule, 1/p x here).
                "billed_bits_per_round": schedule_round_bits(sched, d, q),
            }}
            if impl == "sparse":
                arm["realized_wire_bits"] = plan_round_bits(plan, d, q)
            out[f"{{impl}}_b{{bits}}"] = arm
    for bits in (32, 8):
        dn, sp = out[f"dense_b{{bits}}"], out[f"sparse_b{{bits}}"]
        out[f"wire_ratio_dense_over_sparse_b{{bits}}"] = (
            dn["wire_bytes_per_device"] / max(sp["wire_bytes_per_device"], 1e-9))
    out["speedup_sparse_over_dense_b8"] = (
        out["dense_b8"]["us_per_round"] / out["sparse_b8"]["us_per_round"])
    print("JSON::" + json.dumps(out))
"""


_BLOCK_SRC = """
    import json, warnings
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from benchmarks.common import timeit_best
    from repro.core import (MixerConfig, QuantConfig, TopologySchedule,
                            make_mixer, plan_round_bits)
    from repro.core.topology import ring_graph
    from repro.launch.hlo_stats import collect_collectives

    warnings.filterwarnings("ignore",
                            message="Some donated buffers were not usable")
    m, shards, d, iters = {m}, {shards}, {d}, {iters}
    mesh = Mesh(np.array(jax.devices()[:shards]), ("clients",))
    sched = TopologySchedule.edge_sample(ring_graph(m), p_edge=0.5)
    plan = sched.gossip_plan()
    bp = plan.block_plan(shards)
    sh = NamedSharding(mesh, P("clients", None))
    x_host = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (m, d)))
    z = jax.device_put(jax.random.normal(jax.random.PRNGKey(1), (m, d)), sh)
    out = {{"m": m, "n_shards": shards, "clients_per_shard": bp.m_local,
            "d": d, "schedule": sched.name,
            "block_collectives": bp.num_collectives,
            "block_wire_lane_slots": bp.num_wire_lane_slots,
            "boundary_directed_edges":
                ring_graph(m).block_boundary_edges(bp.m_local)}}
    for bits in (32, 8):
        q = (QuantConfig(bits=bits, stochastic=False, delta_mode="eq7")
             if bits < 32 else None)
        for impl in ("dense", "sparse"):
            mx = make_mixer(sched, MixerConfig(impl=impl, quant=q),
                            mesh=mesh if impl == "sparse" else None,
                            client_axes=("clients",))
            fn = jax.jit(lambda a, b, k, t: mx({{"w": a}}, {{"w": b}},
                                               k, t)[0]["w"],
                         donate_argnums=(0,))
            key = jax.random.PRNGKey(2)
            x = jax.device_put(x_host, sh)
            txt = fn.lower(x, z, key, 0).compile().as_text()
            stats = collect_collectives(txt).as_dict()
            r = jax.block_until_ready(fn(x, z, key, 0))
            us, r = timeit_best(lambda t, r: fn(r, z, key, t), r,
                                iters=iters, reps=3)
            arm = {{"wire_bytes_per_device": stats["wire_bytes"],
                    "collectives": stats["counts"],
                    "us_per_round": us}}
            if impl == "sparse":
                arm["realized_wire_bits"] = plan_round_bits(
                    plan, d, q, clients_per_shard=bp.m_local)
            out[f"{{impl}}_b{{bits}}"] = arm
    for bits in (32, 8):
        dn, sp = out[f"dense_b{{bits}}"], out[f"sparse_b{{bits}}"]
        out[f"wire_ratio_dense_over_block_b{{bits}}"] = (
            dn["wire_bytes_per_device"] /
            max(sp["wire_bytes_per_device"], 1e-9))
    print("JSON::" + json.dumps(out))
"""


_MESH2D_SRC = """
    import json, warnings
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from benchmarks.common import timeit_best
    from repro.core import MixingSpec, QuantConfig, plan_round_bits
    from repro.core.mixing import make_plan_mixer
    from repro.launch.hlo_stats import collect_collectives

    warnings.filterwarnings("ignore",
                            message="Some donated buffers were not usable")
    m, mp, d, iters = {m}, {mp}, {d}, {iters}
    cps = m // 2
    plan = MixingSpec.ring(m, self_weight=0.5).gossip_plan()
    mesh1 = Mesh(np.array(jax.devices()[:2]), ("clients",))
    mesh2 = Mesh(np.array(jax.devices()[:2 * mp]).reshape(2, mp),
                 ("clients", "model"))
    ps2 = {{"w": P("clients", "model")}}
    x_host = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (m, d)))
    z_host = x_host + 0.1
    key = jax.random.PRNGKey(2)
    out = {{"m": m, "model_parallel": mp, "d": d,
            "plan_wire_edges": plan.num_directed_wire_edges}}

    def payload_permute_bytes(txt, min_bytes=1024):
        # Payload ppermutes only: GSPMD also exchanges word-sized RNG
        # keys along the model axis — real but negligible traffic that
        # would mask the per-device wire ratio the gate pins.
        st = collect_collectives(txt).as_dict()
        assert st["by_kind"].get("all-gather", 0.0) == 0.0, st
        return sum(b for kind, b in st["per_op"]
                   if kind == "collective-permute" and b >= min_bytes)

    for bits in (32, 8):
        q = (QuantConfig(bits=bits, stochastic=False, delta_mode="eq7")
             if bits < 32 else None)
        for arm, mesh, specs in (("mesh1d", mesh1, None),
                                 ("mesh2d", mesh2, ps2)):
            mx = make_plan_mixer(plan, mesh, param_specs=specs, quant=q)
            sh = NamedSharding(mesh, P("clients") if specs is None
                               else ps2["w"])
            fn = jax.jit(lambda a, b, k: mx({{"w": a}}, {{"w": b}}, k)["w"],
                         donate_argnums=(0,))
            x = jax.device_put(x_host, sh)
            z = jax.device_put(z_host, sh)
            txt = fn.lower(x, z, key).compile().as_text()
            wire = payload_permute_bytes(txt)
            r = jax.block_until_ready(fn(x, z, key))
            us, r = timeit_best(lambda t, r: fn(r, z, key), r,
                                iters=iters, reps=3)
            out[f"{{arm}}_b{{bits}}"] = {{
                "payload_permute_bytes_per_device": wire,
                "us_per_round": us,
                "billed_bits_per_device_column": plan_round_bits(
                    plan, d, q, clients_per_shard=cps,
                    model_parallel=1 if specs is None else mp),
            }}
    for bits in (32, 8):
        a, b = out[f"mesh1d_b{{bits}}"], out[f"mesh2d_b{{bits}}"]
        out[f"wire_ratio_1d_over_2d_b{{bits}}"] = (
            a["payload_permute_bytes_per_device"] /
            max(b["payload_permute_bytes_per_device"], 1e-9))
    print("JSON::" + json.dumps(out))
"""


def mesh2d_compare(smoke: bool = False) -> dict:
    """2D (clients x model) mesh vs the 1D client mesh: the same ring
    plan mixed with params model-sharded over 4 device columns. Each
    boundary ppermute then ships only the column's 1/mp slice, so
    per-device payload wire bytes drop exactly 4x for fp32 and >= 3x
    for q8 (the lane-block scale rows are shared, not sliced). Gated at
    the source AND re-checked by ci.yml on the artifact; lands under the
    ``mesh2d`` key of BENCH_gossip.json."""
    m, mp = 8, 4
    d = 16384 if smoke else 65536
    iters = 10 if smoke else 20
    res = _run_json_subprocess(
        _MESH2D_SRC.format(m=m, mp=mp, d=d, iters=iters), 2 * mp)
    assert res["wire_ratio_1d_over_2d_b32"] == float(mp), res
    assert res["wire_ratio_1d_over_2d_b8"] >= 3.0, res
    return res


def telemetry_overhead_compare(smoke: bool = False) -> dict:
    """with_telemetry=True vs the plain round on a representative
    training round (the paper's 2NN, q8 stochastic, edge-sampled ring):
    the telemetry pytree adds a consensus reduction and a full quantizer
    replay, and the CI gate holds the wall-clock overhead at <= 1.10x.
    The replay is a fixed cost per round (one extra codec pass over the
    m*d wire deltas), so the batch is sized (64) to make local SGD carry
    its training-realistic share of the round — at toy batch sizes the
    codec dominates the round and the ratio measures the codec against
    itself. Interleaved best-of-7 (``timeit_best`` at reps=1 per
    alternation) so shared-runner noise cancels out of the gated ratio.
    Lands under the ``telemetry`` key of BENCH_gossip.json."""
    import jax
    import jax.numpy as jnp

    from repro.core import (DFedAvgMConfig, init_round_state,
                            make_round_step)
    from repro.data import FederatedDataset, classification_dataset
    from repro.models.paper_nets import init_2nn

    try:
        from .common import loss_2nn, timeit_best
    except ImportError:
        from benchmarks.common import loss_2nn, timeit_best

    m, K, batch = 16, 4, 64
    iters = 3 if smoke else 10
    data = classification_dataset(n=2000 if smoke else 8000, seed=0)
    fed = FederatedDataset.make(data, m, iid=True, seed=0)
    batches = fed.round_batches(0, K=K, batch=batch, seed=0)
    sched = TopologySchedule.edge_sample(ring_graph(m), p_edge=0.5)
    cfg = DFedAvgMConfig(eta=0.05, theta=0.9, local_steps=K,
                         quant=QuantConfig(bits=8))
    p0 = init_2nn(jax.random.PRNGKey(0))
    stacked = jax.tree.map(
        lambda t: jnp.broadcast_to(t[None], (m,) + t.shape), p0)
    arms = {}
    for name, wt in (("off", False), ("on", True)):
        step = jax.jit(make_round_step(loss_2nn, cfg, sched,
                                       with_telemetry=wt))
        st = init_round_state(stacked, jax.random.PRNGKey(1))
        st, mt = step(st, batches)                      # compile
        jax.block_until_ready(mt["loss"])
        arms[name] = {"step": step, "st": st, "us": float("inf")}
    for _ in range(7):
        for name in ("off", "on"):
            a = arms[name]
            us, a["st"] = timeit_best(
                lambda i, st, step=a["step"]: step(st, batches)[0],
                a["st"], iters=iters, reps=1)
            a["us"] = min(a["us"], us)
    return {"m": m, "K": K, "bits": 8, "batch": batch,
            "us_off": arms["off"]["us"], "us_on": arms["on"]["us"],
            "overhead_ratio": arms["on"]["us"] / arms["off"]["us"]}


def block_gossip_compare(smoke: bool = False) -> dict:
    """Block-sharded m=64 over 8 CPU host devices (clients_per_shard=8):
    the sparse backend runs with 8x fewer devices than clients, and its
    wire stays O(n_shards * boundary_degree) — gated in CI against the
    dense O(m) arm. Results land under the ``block64`` key of
    BENCH_gossip.json (same uploaded artifact)."""
    m, shards = 64, 8
    d = 16384 if smoke else 65536
    iters = 5 if smoke else 20
    res = _run_json_subprocess(
        _BLOCK_SRC.format(m=m, shards=shards, d=d, iters=iters), shards)
    # The O(boundary-degree) gate, asserted at the source: the block plan
    # ships exactly the graph's block-boundary edges (no O(m) leak) and
    # the realized q8 wire is far under the dense all-gather.
    assert res["block_wire_lane_slots"] == res["boundary_directed_edges"], \
        (res["block_wire_lane_slots"], res["boundary_directed_edges"])
    assert res["wire_ratio_dense_over_block_b8"] >= 8.0, res
    return res


def placement_compare(smoke: bool = False) -> dict:
    """Compile-time placement pass on irregular graphs: m=64 clients
    over 8 shards (clients_per_shard=8), boundary wire lane slots and
    realized q8 wire bytes of the block realization under the default
    CONTIGUOUS lane layout vs the graph-PARTITIONED placement
    (``compute_placement``: greedy block growth + Kernighan-Lin
    boundary refinement, pure numpy at plan-compile time — no mesh, no
    training, so smoke and full runs are identical). The edge-sampled
    Erdős–Rényi arm is the CI-gated one: its support scatters across a
    contiguous split, and the partition must ship at most HALF its
    boundary lane slots. The small-world arm (ring + random chords) is
    reported unguarded — a ring is already contiguous-optimal, so the
    chords' cut is largely irreducible and the expected ratio is ~1 (the
    pass never does worse: the contiguous candidate is always in the
    pool). Lands under the ``placement`` key of BENCH_gossip.json."""
    del smoke  # compile-time numpy only — same cost either way
    import numpy as np

    from repro.core import compute_placement
    from repro.core.comm_cost import plan_round_bits
    from repro.core.gossip_plan import plan_from_support
    from repro.core.topology import Graph

    m, shards, d = 64, 8, 16384
    cps = m // shards
    q8 = QuantConfig(bits=8)

    def ring_with_chords(n_chords: int, seed: int) -> Graph:
        adj = np.asarray(ring_graph(m).adj).copy()
        rng = np.random.default_rng(seed)
        added = 0
        while added < n_chords:
            i, j = (int(v) for v in rng.integers(0, m, size=2))
            if i != j and not adj[i, j]:
                adj[i, j] = adj[j, i] = True
                added += 1
        return Graph(adj, name=f"ring{m}+{n_chords}chords")

    arms = {
        "er": erdos_renyi_graph(m, 0.06, seed=2),
        "ring_chords": ring_with_chords(16, seed=7),
    }
    out = {"m": m, "n_shards": shards, "d": d, "bits": 8}
    for name, g in arms.items():
        plan = plan_from_support(g, name=g.name)
        pl = compute_placement(g, shards)
        cont = plan.block_plan(shards).num_wire_lane_slots
        part = plan.block_plan(shards, placement=pl).num_wire_lane_slots
        out[name] = {
            "graph": g.name,
            "directed_edges": g.num_directed_edges(),
            "contiguous_boundary_lane_slots": cont,
            "partition_boundary_lane_slots": part,
            "boundary_ratio_contiguous_over_partition":
                cont / max(part, 1),
            "contiguous_wire_bytes_q8": plan_round_bits(
                plan, d, q8, clients_per_shard=cps) / 8.0,
            "partition_wire_bytes_q8": plan_round_bits(
                plan, d, q8, clients_per_shard=cps, placement=pl) / 8.0,
            "contiguous_boundary_edges": g.block_boundary_edges(cps),
            "partition_boundary_edges": g.block_boundary_edges(cps,
                                                               perm=pl),
        }
    # The tentpole gate, asserted at the source (ci.yml re-checks it on
    # the uploaded artifact): >= 2x fewer boundary lane slots on the ER
    # arm.
    er = out["er"]
    assert (er["partition_boundary_lane_slots"]
            <= er["contiguous_boundary_lane_slots"] / 2), er
    return out


def gossip_backend_compare(smoke: bool = False) -> list[tuple]:
    """dense vs sparse on an edge-sampled schedule: HLO wire bytes (the
    O(m) all-gather vs O(degree) ppermute claim), wall clock, and the
    expectation-based vs realized-plan bit billing. Results land in
    BENCH_gossip.json (uploaded as a CI artifact)."""
    m = 8
    # Smoke keeps the subprocess cheap but d must be large enough that
    # the wire/compute asymmetry (m-way gather vs O(degree) ppermute)
    # dominates the fixed per-collective dispatch overhead — at 4096 the
    # two arms are within scheduler noise of each other on a CPU host.
    d = 16384 if smoke else 65536
    iters = 10 if smoke else 20
    res = _run_json_subprocess(_COMPARE_SRC.format(m=m, d=d, iters=iters), m)
    # Block-sharded arm: m=64 clients over the same 8 host devices
    # (clients_per_shard=8) — m past the device count, wire gated at
    # O(n_shards * boundary_degree).
    res["block64"] = block_gossip_compare(smoke=smoke)
    # 2D mesh arm: model-parallel columns vs the 1D client mesh — the
    # per-device wire must shrink ~linearly with the MP degree.
    res["mesh2d"] = mesh2d_compare(smoke=smoke)
    # Telemetry-overhead arm: with_telemetry on vs off, gated <= 1.10x.
    res["telemetry"] = telemetry_overhead_compare(smoke=smoke)
    # Placement arm: contiguous vs partitioned lane layout on irregular
    # graphs (compile-time numpy; ER ratio gated >= 2x).
    res["placement"] = placement_compare(smoke=smoke)
    GOSSIP_JSON.write_text(json.dumps(res, indent=2))
    rows = []
    for bits in (32, 8):
        dn, sp = res[f"dense_b{bits}"], res[f"sparse_b{bits}"]
        rows.append((
            f"gossip_sparse_vs_dense_b{bits}",
            sp["us_per_round"],
            f"sparse_wireB={sp['wire_bytes_per_device']:.0f}|"
            f"dense_wireB={dn['wire_bytes_per_device']:.0f}|"
            f"ratio={res[f'wire_ratio_dense_over_sparse_b{bits}']:.2f}|"
            f"dense_us={dn['us_per_round']:.1f}|"
            f"billed_bits={sp['billed_bits_per_round']:.0f}|"
            f"realized_wire_bits={sp['realized_wire_bits']:.0f}"))
    blk = res["block64"]
    bsp, bdn = blk["sparse_b8"], blk["dense_b8"]
    rows.append((
        "gossip_block64_sparse_vs_dense_b8",
        bsp["us_per_round"],
        f"m={blk['m']}|shards={blk['n_shards']}|"
        f"block_wireB={bsp['wire_bytes_per_device']:.0f}|"
        f"dense_wireB={bdn['wire_bytes_per_device']:.0f}|"
        f"ratio={blk['wire_ratio_dense_over_block_b8']:.2f}|"
        f"boundary_lanes={blk['block_wire_lane_slots']}|"
        f"realized_wire_bits={bsp['realized_wire_bits']:.0f}"))
    m2 = res["mesh2d"]
    m1a, m2a = m2["mesh1d_b8"], m2["mesh2d_b8"]
    rows.append((
        "gossip_mesh2d_vs_1d_b8",
        m2a["us_per_round"],
        f"mp={m2['model_parallel']}|"
        f"wire2dB={m2a['payload_permute_bytes_per_device']:.0f}|"
        f"wire1dB={m1a['payload_permute_bytes_per_device']:.0f}|"
        f"ratio={m2['wire_ratio_1d_over_2d_b8']:.2f}|"
        f"fp32_ratio={m2['wire_ratio_1d_over_2d_b32']:.2f}"))
    tl = res["telemetry"]
    rows.append((
        "round_telemetry_on_vs_off",
        tl["us_on"],
        f"off_us={tl['us_off']:.1f}|"
        f"overhead_ratio={tl['overhead_ratio']:.3f}"))
    for arm in ("er", "ring_chords"):
        pa = res["placement"][arm]
        rows.append((
            f"placement_{arm}_partition_vs_contiguous",
            0.0,
            f"graph={pa['graph']}|"
            f"contig_lanes={pa['contiguous_boundary_lane_slots']}|"
            f"part_lanes={pa['partition_boundary_lane_slots']}|"
            f"ratio={pa['boundary_ratio_contiguous_over_partition']:.2f}|"
            f"contig_q8B={pa['contiguous_wire_bytes_q8']:.0f}|"
            f"part_q8B={pa['partition_wire_bytes_q8']:.0f}"))
    return rows


def run(smoke: bool = False):
    m = 8 if smoke else 16
    rounds = 2 if smoke else 30
    bits = 32
    quant = QuantConfig(bits=bits) if bits < 32 else None
    rows = []
    for name, topo in schedules(m, rounds):
        out = train_dfedavgm_2nn(m=m, K=2 if smoke else 4,
                                 batch=8 if smoke else 32,
                                 rounds=rounds, topology=topo)
        d = out["d"]
        if isinstance(topo, TopologySchedule):
            bpr = schedule_round_bits(topo, d, quant)
        else:
            bpr = dfedavgm_round_bits(topo.graph, d, quant)
        rows.append((f"timevarying_{name}", out["us_per_round"],
                     f"loss={out['loss']:.4f}|"
                     f"consensus_dist={out['consensus_dist']:.3e}|"
                     f"bits_per_round={bpr:.0f}|acc={out['acc']:.3f}"))
    rows.extend(gossip_backend_compare(smoke=smoke))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny m, 2 rounds — CI entrypoint check")
    args = ap.parse_args()
    for name, us, derived in run(smoke=args.smoke):
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
