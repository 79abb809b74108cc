"""2D (clients x model) mesh: tensor-parallel params composed with the
client-block gossip mesh (8 host devices, subprocess — see conftest).

The tentpole claims, each pinned here:

  * PARITY IS BITWISE: the 2D mesh's mixed params equal the 1D client
    mesh's bit for bit — fp32, q8 deterministic (lemma5 AND eq7), and q8
    STOCHASTIC. Three mechanisms make this structural rather than lucky:
    (a) per-leaf quantizer scales derive from a pmax-all-reduced amax
    (max is order-exact), (b) stochastic rounding noise is drawn once in
    the full-leaf geometry outside shard_map and sliced per model column
    by the param specs, (c) the mix itself is elementwise per lane.
  * THE WIRE SHRINKS: boundary ppermutes move only each device column's
    1/model_parallel slice — per-device wire bytes drop ~linearly with
    the model-parallel degree (exactly 1/mp for fp32; quantized rides
    the same stream minus shared lane-block padding).
  * PPERMUTES STAY ON THE CLIENT AXIS: the model axis carries only the
    tiny amax pmax (plus GSPMD's word-sized RNG-key exchanges) — no
    all-gather of params, no f32 wire.
  * END TO END: full DFedAvgM round steps train on the (2, 4) mesh —
    the paper-scale toy net bitwise-equal to 1D, and a sliced production
    config (gemma-7b reduced, strategy-A rules) through the real train
    driver.
"""
import math
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") +
        f" --xla_force_host_platform_device_count={devices}").strip()
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    # A CPU run with fake host devices: never the parent's accelerator.
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


_PRELUDE = """
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import MixingSpec, QuantConfig
    from repro.core.mixing import execute_plan_reference, make_plan_mixer
    M = 8
    mesh1 = Mesh(np.array(jax.devices()[:2]), ("clients",))
    mesh2 = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                 ("clients", "model"))
    # w shards its last dim over the 4 model columns; s is too small to
    # divide and stays replicated (the mixed sharded/replicated case the
    # production configs hit).
    ps2 = {"w": P("clients", None, "model"), "b": P("clients", "model"),
           "s": P("clients", None)}
    k = jax.random.PRNGKey(0)
    kx, kz, kq = jax.random.split(k, 3)
    x = {"w": jax.random.normal(kx, (M, 4, 16)),
         "b": jax.random.normal(kz, (M, 12)),
         "s": jax.random.normal(kq, (M, 3))}
    z = jax.tree.map(lambda a: a + 0.1 * jnp.ones_like(a), x)
    def put2(t):
        return jax.device_put(t, {kn: NamedSharding(mesh2, s)
                                  for kn, s in ps2.items()})
"""

_QUANTS = """
    quants = [("fp32", None),
              ("q8-lemma5", QuantConfig(bits=8, stochastic=False,
                                        delta_mode="lemma5")),
              ("q8-eq7", QuantConfig(bits=8, stochastic=False,
                                     delta_mode="eq7")),
              ("q8-stoch", QuantConfig(bits=8, stochastic=True,
                                       delta_mode="lemma5"))]
"""


def test_2d_mixer_bitwise_equal_to_1d_and_reference():
    """The headline: the same ring plan mixed on the (2, 4) mesh with
    model-sharded params equals the 1D 2-device client mesh BIT FOR BIT
    for every quant mode, and matches the mesh-free plan reference."""
    out = run_sub(_PRELUDE + _QUANTS + """
    spec = MixingSpec.ring(M, self_weight=0.5)
    plan = spec.gossip_plan()
    x2, z2 = put2(x), put2(z)
    for qname, q in quants:
        mix1 = make_plan_mixer(plan, mesh1, quant=q)
        mix2 = make_plan_mixer(plan, mesh2, param_specs=ps2, quant=q)
        o1 = jax.jit(mix1)(x, z, kq)
        o2 = jax.jit(mix2)(x2, z2, kq)
        for kn in o1:
            a, b = np.asarray(o1[kn]), np.asarray(o2[kn])
            assert np.array_equal(a, b), (
                qname, kn, float(np.abs(a - b).max()))
        ref = execute_plan_reference(plan, jnp.asarray(spec.W, jnp.float32),
                                     z, x, q, kq)
        err = max(float(jnp.max(jnp.abs(o2[kn] - ref[kn]))) for kn in o1)
        assert err < 1e-5, (qname, err)
        print("MIX2D_OK", qname)
    """)
    assert out.count("MIX2D_OK") == 4


def test_2d_round_step_bitwise_equal_to_1d():
    """Full DFedAvgM rounds (local heavy-ball SGD under GSPMD + sparse
    gossip inside shard_map) on the (2, 4) mesh vs the 1D client mesh,
    stochastic q8 included. The schedule's sampled events and the
    quantizer's draws are IDENTICAL (partitionable threefry + the pmax'd
    scales + the full-leaf noise input — the mixer-level test above pins
    those bitwise); the end-to-end params agree to float rounding
    (~1 ulp/round), because XLA chooses FMA contraction for the SGD
    arithmetic per compiled module — the same cross-module caveat the
    1D parity suites document."""
    out = run_sub(_PRELUDE + """
    from repro.core import (DFedAvgMConfig, TopologySchedule,
                            init_round_state, make_round_step)
    from repro.core.topology import ring_graph
    D1, D2 = 4, 16
    sched = TopologySchedule.partial(ring_graph(M), 0.6)
    # elementwise gradient: GSPMD partitions it per model column with no
    # cross-column reduction, so 1D and 2D trajectories can be compared
    # bitwise (a contraction would re-associate float sums)
    loss_fn = lambda p, b, r: 0.5 * jnp.sum((p["w"] - b["c"]) ** 2)
    c = jax.random.normal(jax.random.PRNGKey(9), (M, D1, D2))
    batches = {"c": jnp.broadcast_to(c[:, None], (M, 4, D1, D2))}
    for q in (None, QuantConfig(bits=8, stochastic=True,
                                delta_mode="lemma5")):
        cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4, quant=q,
                             mixer_impl="sparse")
        def run(mesh, specs):
            step = jax.jit(make_round_step(loss_fn, cfg, sched, mesh=mesh,
                                           client_axes=("clients",),
                                           param_specs=specs))
            p0 = {"w": jnp.zeros((M, D1, D2))}
            if specs is not None:
                p0 = jax.device_put(p0, {kn: NamedSharding(mesh, s)
                                         for kn, s in specs.items()})
            st = init_round_state(p0, jax.random.PRNGKey(7))
            for _ in range(3):
                st, mt = step(st, batches)
            return np.asarray(st.params["w"]), float(mt["active_frac"])
        w1, af1 = run(mesh1, None)
        w2, af2 = run(mesh2, {"w": P("clients", None, "model")})
        assert af1 == af2, (af1, af2)   # identical sampled participation
        err = float(np.max(np.abs(w1 - w2)))
        assert err < 1e-6, err
        print("ROUND2D_OK", "q8" if q else "fp32", err)
    """)
    assert out.count("ROUND2D_OK") == 2


def test_2d_hlo_boundary_permutes_move_local_slice_only():
    """The wire pin on compiled HLO: every payload-sized boundary
    ppermute on the 2D mesh carries the LOCAL model slice — fp32 wire
    bytes are exactly 1/model_parallel of the 1D program's, quantized
    payload permutes shrink >= 3x (shared lane-block padding keeps it
    off the exact 4), and the model axis adds no all-gather and no f32
    wire — only the scalar-per-leaf amax all-reduce (pmax) plus GSPMD's
    word-sized RNG-key exchanges."""
    out = run_sub("""
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import MixingSpec, QuantConfig
    from repro.core.mixing import make_plan_mixer
    from repro.launch.hlo_stats import collect_collectives
    M, D = 8, 8192
    plan = MixingSpec.ring(M, self_weight=0.5).gossip_plan()
    mesh1 = Mesh(np.array(jax.devices()[:2]), ("clients",))
    mesh2 = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                 ("clients", "model"))
    ps2 = {"w": P("clients", None, "model")}
    x = {"w": jax.random.normal(jax.random.PRNGKey(0), (M, 4, D))}
    z = jax.tree.map(lambda a: a + 0.1, x)
    kq = jax.random.PRNGKey(1)
    def put2(t):
        return jax.device_put(t, {kn: NamedSharding(mesh2, s)
                                  for kn, s in ps2.items()})
    def perm_bytes(txt, min_bytes=1024):
        st = collect_collectives(txt).as_dict()
        assert st["by_kind"].get("all-gather", 0.0) == 0.0, st
        big = [b for k, b in st["per_op"] if k == "collective-permute"
               and b >= min_bytes]
        small = [b for k, b in st["per_op"] if k == "collective-permute"
                 and b < min_bytes]
        return sum(big), len(big), small, st
    for qname, q in [("fp32", None),
                     ("q8", QuantConfig(bits=8, stochastic=True))]:
        mix1 = make_plan_mixer(plan, mesh1, quant=q)
        mix2 = make_plan_mixer(plan, mesh2, param_specs=ps2, quant=q)
        t1 = jax.jit(mix1).lower(x, z, kq).compile().as_text()
        t2 = jax.jit(mix2).lower(put2(x), put2(z), kq).compile().as_text()
        b1, n1, _, s1 = perm_bytes(t1)
        b2, n2, small2, s2 = perm_bytes(t2)
        assert n2 == n1, (qname, n1, n2)         # same boundary schedule
        if qname == "fp32":
            assert b2 * 4 == b1, (b1, b2)        # exactly the 1/mp slice
        else:
            assert b2 * 3 <= b1, (b1, b2)
            # quantized wire stays u32: no f32 payload permute leaked
            assert all("f32[" not in l.split("=", 1)[1][:24]
                       for l in t2.splitlines()
                       if "collective-permute(" in l and "-done(" not in l)
        # model-axis traffic: word-sized key exchanges at most
        assert all(b <= 128 for b in small2), small2
        print("HLO2D_OK", qname, b1, "->", b2)
    """)
    assert out.count("HLO2D_OK") == 2


def test_2d_paper_net_trains_sparse_equals_dense():
    """The paper's 2NN end to end on the (2, 4) mesh: hidden dims shard
    over the model columns (both weight orientations — output-dim AND
    contraction-dim sharded), quantized-free so the only divergence vs
    the dense host reference is the sharded matmuls' partial-sum
    re-association. Sparse-2D training must match the dense mixer's
    trajectory to float rounding and the loss must move."""
    out = run_sub("""
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import (DFedAvgMConfig, MixingSpec, TopologySchedule,
                            init_round_state, make_round_step)
    from repro.core.topology import ring_graph
    from repro.models.paper_nets import apply_2nn, init_2nn
    M, B, K = 8, 4, 2
    mesh2 = Mesh(np.array(jax.devices()).reshape(2, 4),
                 ("clients", "model"))
    ps2 = {"w1": P("clients", None, "model"), "b1": P("clients", "model"),
           "w2": P("clients", "model", None), "b2": P("clients", "model"),
           "w3": P("clients", "model", None), "b3": P("clients", "model")}
    p0 = init_2nn(jax.random.PRNGKey(0), d_in=32, d_hidden=16,
                  n_classes=8)
    stacked = jax.tree.map(
        lambda t: jnp.broadcast_to(t[None], (M,) + t.shape), p0)
    kx, ky = jax.random.split(jax.random.PRNGKey(3))
    batches = {"x": jax.random.normal(kx, (M, K, B, 32)),
               "y": jax.random.randint(ky, (M, K, B), 0, 8)}
    def loss_fn(p, b, r):
        logits = apply_2nn(p, b["x"])
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(
            logp, b["y"][:, None], axis=-1))
    sched = TopologySchedule.edge_sample(ring_graph(M), p_edge=0.7)
    def run(impl, mesh, specs):
        cfg = DFedAvgMConfig(eta=0.1, theta=0.9, local_steps=K,
                             mixer_impl=impl)
        step = jax.jit(make_round_step(
            loss_fn, cfg, sched, mesh=mesh,
            client_axes=("clients",) if mesh else None,
            param_specs=specs))
        p = stacked
        if specs is not None:
            p = jax.device_put(p, {kn: NamedSharding(mesh, s)
                                   for kn, s in specs.items()})
        st = init_round_state(p, jax.random.PRNGKey(11))
        losses = []
        for _ in range(3):
            st, mt = step(st, batches)
            losses.append(float(mt["loss"]))
        return st.params, losses
    pd, ld = run("dense", None, None)
    p2, l2 = run("sparse", mesh2, ps2)
    for kn in pd:
        a, b = np.asarray(pd[kn]), np.asarray(p2[kn])
        err = float(np.max(np.abs(a - b)))
        assert err < 2e-5, (kn, err)
    assert l2[-1] < l2[0], l2
    print("PAPER2D_OK", l2)
    """)
    assert "PAPER2D_OK" in out


def test_2d_train_driver_production_config():
    """The sliced production config end to end through the real CLI
    driver: gemma-7b (reduced) on the (2, 4) mesh, strategy-A rules
    sharding 8/11 leaves, quantized gossip — trains, logs the 2D mesh
    line and the per-device wire reduction, the loss moves, and every
    round hits the one dispatch-cache entry."""
    out = run_sub("""
    from repro.launch.train import main
    res = main(["--arch", "gemma-7b", "--reduced", "--clients", "2",
                "--model-parallel", "4", "--rounds", "3", "--bits", "8",
                "--local-steps", "2", "--batch", "2", "--seq", "16"])
    print("STEP_SIGNATURES", res.step._cache_size())
    """, timeout=900)
    # The model-sharded params go in as they come out: every round hits
    # the one entry of jit's dispatch cache.
    assert "STEP_SIGNATURES 1" in out, out
    assert "2D mesh: model_parallel=4" in out
    assert "param leaves model-sharded" in out
    assert "4.0x reduction" in out
    losses = [float(l.split("loss=")[1].split()[0])
              for l in out.splitlines() if "loss=" in l]
    assert len(losses) == 3 and all(math.isfinite(v) for v in losses)
    assert losses[-1] < losses[0]


def test_fused_tail_rejects_model_sharded_specs():
    """fuse_round computes the last gradient inside the client shard_map
    body, which would only see a 1/mp model slice — the 2D mesh must
    refuse it loudly, not silently mis-train."""
    out = run_sub(_PRELUDE + """
    from repro.core import DFedAvgMConfig, TopologySchedule, make_round_step
    from repro.core.topology import ring_graph
    sched = TopologySchedule.constant(MixingSpec.ring(M, self_weight=0.5))
    loss_fn = lambda p, b, r: 0.5 * jnp.sum((p["w"] - b["c"]) ** 2)
    cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4,
                         mixer_impl="sparse", fuse_round=True)
    try:
        make_round_step(loss_fn, cfg, sched, mesh=mesh2,
                        client_axes=("clients",),
                        param_specs={"w": P("clients", None, "model")})
    except ValueError as e:
        assert "model-sharded" in str(e), e
        print("FUSE2D_REJECT_OK")
    """)
    assert "FUSE2D_REJECT_OK" in out
