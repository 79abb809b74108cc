"""Sparse GossipPlan backend on a real CPU mesh (8 host devices, run in a
SUBPROCESS so the main test process keeps seeing 1 device — see conftest).

Unlike test_sharding_multidev these need neither jax.set_mesh nor
jax.make_mesh, so they run on every supported jax release: the sparse
backend only uses shard_map with an explicit Mesh.

Covers the acceptance matrix of the plan/compile/execute refactor:
  * every TopologySchedule kind x {fp32, q8-lemma5, q8-eq7, q8-stochastic}
    matches the dense reference over several rounds (stochastic rounding
    draws the SAME bits: the key derivation is shared)
  * static ring/torus specs lowered through the plan pipeline match the
    pre-refactor dense-equivalent semantics, quantized included (the old
    quantized torus silently fell back to dense; now it moves packed
    uint32 words through ppermutes — asserted on the HLO)
  * HLO collective stats: the sparse backend moves O(degree) ppermute
    bytes and NO all-gather where the dense path all-gathers O(m)
  * BLOCK SHARDING (m > device count): m=32 clients over 8 shards
    (m_local=4) match dense and the mesh-free reference for every
    schedule kind x quant mode, and a contiguous-blocked ring's HLO
    ships only boundary lanes — O(n_shards * boundary_degree) wire
    bytes, not O(m)
"""
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
    from repro.core import (MixerConfig, MixingSpec, QuantConfig,
                            TopologySchedule, make_mixer, mix_dense)
    from repro.core.mixing import _mix_dense_quantized
    from repro.core.topology import erdos_renyi_graph, ring_graph
    M, D = 8, 33
    mesh = Mesh(np.array(jax.devices()[:M]), ("clients",))
    x = jax.random.normal(jax.random.PRNGKey(0), (M, D))
    z = jax.random.normal(jax.random.PRNGKey(1), (M, D))
"""


def test_sparse_matches_dense_every_schedule_kind():
    """The headline equivalence: sparse == dense for every schedule kind,
    quantized (both recursions, deterministic AND stochastic) and not."""
    out = run_sub(_PRELUDE + """
    ring = MixingSpec.ring(M, self_weight=0.5)
    er = erdos_renyi_graph(M, 0.5, seed=3)
    scheds = [TopologySchedule.constant(ring),
              TopologySchedule.edge_sample(er, 0.6),
              TopologySchedule.partial(ring_graph(M), 0.5),
              TopologySchedule.random_walk(ring_graph(M), horizon=16, seed=1),
              TopologySchedule.cycle([ring, MixingSpec.torus(2, M // 2)])]
    quants = [None,
              QuantConfig(bits=8, stochastic=False, delta_mode="lemma5"),
              QuantConfig(bits=8, stochastic=False, delta_mode="eq7"),
              QuantConfig(bits=8, stochastic=True, delta_mode="lemma5")]
    for sched in scheds:
        for q in quants:
            mx_s = make_mixer(sched, MixerConfig(impl="sparse", quant=q),
                              mesh=mesh, client_axes=("clients",))
            mx_d = make_mixer(sched, MixerConfig(impl="dense", quant=q))
            js, jd = jax.jit(mx_s), jax.jit(mx_d)
            for t in range(3):
                key = jax.random.PRNGKey(10 * t + 3)
                a, act_a = js({"w": x}, {"w": z}, key, t)
                b, act_b = jd({"w": x}, {"w": z}, key, t)
                err = float(jnp.max(jnp.abs(a["w"] - b["w"])))
                assert err < 1e-5, (sched.name, q, t, err)
                assert np.array_equal(np.asarray(act_a), np.asarray(act_b))
        print("KIND_OK", sched.name)
    print("ALL_KINDS_OK")
    """)
    assert "ALL_KINDS_OK" in out and out.count("KIND_OK") == 5


def test_static_ring_torus_plans_match_reference():
    """Static specs through the plan pipeline: identical semantics to the
    dense reference, quantized included (previously bespoke mixers)."""
    out = run_sub(_PRELUDE + """
    quants = [None,
              QuantConfig(bits=8, stochastic=False, delta_mode="lemma5"),
              QuantConfig(bits=8, stochastic=False, delta_mode="eq7"),
              QuantConfig(bits=8, stochastic=True, delta_mode="lemma5")]
    for spec in (MixingSpec.ring(M, self_weight=0.5), MixingSpec.torus(2, 4)):
        for q in quants:
            mx = make_mixer(spec, MixerConfig(impl="auto", quant=q),
                            mesh=mesh, client_axes=("clients",))
            key = jax.random.PRNGKey(5)
            o = jax.jit(mx)({"w": x}, {"w": z}, key)["w"]
            if q is None:
                ref = mix_dense(spec.W, {"w": z})["w"]
            else:
                ref = _mix_dense_quantized(spec.W, {"w": x}, {"w": z}, q,
                                           key)["w"]
            err = float(jnp.max(jnp.abs(o - ref)))
            assert err < 1e-5, (spec.graph.name, q, err)
        print("STATIC_OK", spec.graph.name)
    """)
    assert out.count("STATIC_OK") == 2


def test_quantized_torus_routes_through_sparse_u32_wire():
    """The satellite fix: quantized torus no longer falls back to dense —
    its HLO moves packed uint32 words through collective-permutes."""
    out = run_sub(_PRELUDE + """
    spec = MixingSpec.torus(2, 4)
    q = QuantConfig(bits=8, stochastic=False, delta_mode="eq7")
    mx = make_mixer(spec, MixerConfig(impl="torus", quant=q), mesh=mesh,
                    client_axes=("clients",))
    txt = jax.jit(mx).lower({"w": x}, {"w": z},
                            jax.random.PRNGKey(0)).compile().as_text()
    perms = [l for l in txt.splitlines() if "collective-permute(" in l]
    u32 = [l for l in perms if "u32[" in l.split("=", 1)[1][:24]]
    assert perms, "quantized torus fell back to dense (no ppermutes)"
    assert u32, "no u32 wire permutes: " + perms[0]
    assert "all-gather" not in txt
    print("TORUS_WIRE_OK", len(perms), len(u32))
    """)
    assert "TORUS_WIRE_OK" in out


def test_sparse_moves_o_degree_bytes_vs_dense_o_m():
    """Edge-sampled schedule: dense lowers to an m-way gather; the sparse
    plan moves only degree-many neighbor messages per round."""
    out = run_sub(_PRELUDE + """
    from repro.launch.hlo_stats import collect_collectives
    sched = TopologySchedule.edge_sample(ring_graph(M), 0.5)
    sh = NamedSharding(mesh, P("clients", None))
    xs, zs = jax.device_put(x, sh), jax.device_put(z, sh)
    wire = {}
    for impl in ("dense", "sparse"):
        mx = make_mixer(sched, MixerConfig(impl=impl),
                        mesh=mesh if impl == "sparse" else None,
                        client_axes=("clients",))
        fn = jax.jit(lambda a, b, k: mx({"w": a}, {"w": b}, k, 0)[0]["w"])
        txt = fn.lower(xs, zs, jax.random.PRNGKey(0)).compile().as_text()
        wire[impl] = collect_collectives(txt).as_dict()
    sp, dn = wire["sparse"], wire["dense"]
    assert sp["by_kind"].get("all-gather", 0.0) == 0.0
    assert set(sp["by_kind"]) == {"collective-permute"}
    # ring plan: 2 ppermute steps x D floats; dense: m-way data movement
    assert sp["counts"]["collective-permute"] == 2
    assert sp["wire_bytes"] < dn["wire_bytes"] / 3, (sp, dn)
    print("WIREBYTES_OK", sp["wire_bytes"], dn["wire_bytes"])
    """)
    assert "WIREBYTES_OK" in out


def test_flat_wire_parity_vs_plan_reference():
    """The tentpole parity matrix, for every schedule kind x {fp32, q8
    det, q8 stochastic} x both codec backends: the flat-buffer mix
    matches ``execute_plan_reference`` — the WIRE (quantization
    decisions: packed words and scales, checked below in
    test_flat_wire_words_bitwise...) is bit-identical, and the fused
    float output agrees to a few ulp (XLA chooses FMA contraction per
    compiled module, so bitwise float equality across the shard_map body
    and the mesh-free reference is not a property XLA offers). W_t is
    pre-sampled and fed through make_event_mixer so both sides consume
    the identical event matrix."""
    out = run_sub(_PRELUDE + """
    from repro.core import execute_plan_reference
    from repro.core.mixing import make_event_mixer
    xt = {"w": x, "b": jax.random.normal(jax.random.PRNGKey(4), (M, 3, 2))}
    zt = {"w": z, "b": jax.random.normal(jax.random.PRNGKey(5), (M, 3, 2))}
    ring = MixingSpec.ring(M, self_weight=0.5)
    er = erdos_renyi_graph(M, 0.5, seed=3)
    scheds = [TopologySchedule.constant(ring),
              TopologySchedule.edge_sample(er, 0.6),
              TopologySchedule.partial(ring_graph(M), 0.5),
              TopologySchedule.random_walk(ring_graph(M), horizon=16,
                                           seed=1),
              TopologySchedule.cycle([ring, MixingSpec.torus(2, M // 2)])]
    quants = [None,
              QuantConfig(bits=8, stochastic=False, delta_mode="eq7"),
              QuantConfig(bits=8, stochastic=True, delta_mode="lemma5")]
    for sched in scheds:
        plan = sched.gossip_plan()
        W_t, active, key_q = jax.jit(sched.round_event)(
            jax.random.PRNGKey(37), 1)
        for q in quants:
            def ref_fn(x, z, W, active, key, q=q):
                z_eff = jax.tree.map(
                    lambda zl, xl: jnp.where(
                        active.reshape((-1,) + (1,) * (zl.ndim - 1)) > 0,
                        zl, xl), z, x)
                return execute_plan_reference(plan, W, z_eff, x=x,
                                              quant=q, key=key)
            ref = jax.jit(ref_fn)(xt, zt, W_t, active, key_q)
            for wire in ("planar", "seq"):
                ev = make_event_mixer(M, quant=q, mesh=mesh,
                                      client_axes=("clients",), plan=plan,
                                      wire=wire, gate=True)
                got = jax.jit(ev)(xt, zt, W_t, active, key_q)
                err = max(float(jnp.max(jnp.abs(got[k] - ref[k])))
                          for k in xt)
                assert err < 1e-6, (wire, sched.name, q, err)
        print("PARITY_OK", sched.name)
    """, timeout=1200)
    assert out.count("PARITY_OK") == 5


def test_flat_wire_words_bitwise_mesh_vs_reference():
    """The bit-identity that IS structural: the wire itself. The packed
    uint32 words and per-leaf scales the shard_map body produces equal
    the reference layout's encode bit for bit (quantize = single
    correctly-rounded ops: subtract, divide, floor, compare — no
    accumulation, so no FMA freedom), stochastic rounding included."""
    out = run_sub(_PRELUDE + """
    from repro.core.wire_layout import WireLayout
    from repro.core.mixing import _quant_leaf_keys
    sm = jax.shard_map
    xt = {"w": x, "b": jax.random.normal(jax.random.PRNGKey(4), (M, 3, 2))}
    zt = {"w": z, "b": jax.random.normal(jax.random.PRNGKey(5), (M, 3, 2))}
    for q in (QuantConfig(bits=8, stochastic=False, delta_mode="eq7"),
              QuantConfig(bits=8, stochastic=True, delta_mode="lemma5")):
        key = jax.random.PRNGKey(11)
        nl = len(jax.tree.leaves(xt))
        keys_cm = jnp.transpose(_quant_leaf_keys(key, nl, M), (1, 0, 2))

        def body(xb, zb, kb, q=q):
            xc = jax.tree.map(lambda a: a[0], xb)
            zc = jax.tree.map(lambda a: a[0], zb)
            layout = WireLayout.for_tree(xc, bits=q.bits)
            delta = layout.to_planar(jax.tree.map(
                lambda zl, xl: zl - xl, zc, xc))
            scales = layout.leaf_scales(delta, q)
            leaf_keys = kb[0] if q.stochastic else None
            words = layout.encode(delta, scales, q, leaf_keys=leaf_keys)
            return words[None], scales[None]

        specs = jax.tree.map(
            lambda l: P("clients", *([None] * (l.ndim - 1))), xt)
        fn = sm(body, mesh=mesh,
                in_specs=(specs, specs, P("clients", None, None)),
                out_specs=(P("clients", None), P("clients", None)))
        wm, sm_out = jax.jit(fn)(xt, zt, keys_cm)

        def ref_fn(xt, zt, key, q=q):
            layout = WireLayout.for_tree(
                jax.tree.map(lambda l: l[0], xt), bits=q.bits)
            delta = layout.to_planar_stacked(jax.tree.map(
                lambda zl, xl: zl - xl, zt, xt))
            scales = layout.leaf_scales(delta, q)
            lk = (_quant_leaf_keys(key, layout.n_leaves, M)
                  if q.stochastic else None)
            return layout.encode(delta, scales, q, leaf_keys=lk), scales
        wr, sr = jax.jit(ref_fn)(xt, zt, key)
        assert np.array_equal(np.asarray(wm), np.asarray(wr)), q
        assert np.array_equal(np.asarray(sm_out), np.asarray(sr)), q
        print("WIRE_BITWISE_OK", q.delta_mode, q.stochastic)
    """)
    assert out.count("WIRE_BITWISE_OK") == 2


def test_quantized_sparse_round_one_permute_per_plan_step():
    """The wire-path invariant the flat buffer buys: a quantized sparse
    round issues EXACTLY ONE collective-permute per plan step for the
    WHOLE MODEL — scales (and lemma5 replicas) ride the u32 stream tail,
    and no leaf multiplies the collective count (the per-leaf path
    launched 2 x n_leaves x n_steps collectives). The wire is u32-only:
    no f32 ppermutes, no full-size f32 dequant streams, no all-gather."""
    out = run_sub(_PRELUDE + """
    from repro.launch.hlo_stats import collect_collectives
    xt = {"w": x, "b": jax.random.normal(jax.random.PRNGKey(4), (M, 3, 2)),
          "c": jax.random.normal(jax.random.PRNGKey(6), (M, 7))}
    zt = {"w": z, "b": jax.random.normal(jax.random.PRNGKey(5), (M, 3, 2)),
          "c": jax.random.normal(jax.random.PRNGKey(7), (M, 7))}
    sched = TopologySchedule.edge_sample(ring_graph(M), 0.5)
    plan = sched.gossip_plan()
    for q in (QuantConfig(bits=8, stochastic=False, delta_mode="eq7"),
              QuantConfig(bits=8, stochastic=True, delta_mode="lemma5")):
        mx = make_mixer(sched, MixerConfig(impl="sparse", quant=q),
                        mesh=mesh, client_axes=("clients",))
        fn = jax.jit(lambda a, b, k, t: mx(a, b, k, t)[0])
        txt = fn.lower(xt, zt, jax.random.PRNGKey(0), 0).compile().as_text()
        stats = collect_collectives(txt).as_dict()
        assert set(stats["counts"]) == {"collective-permute"}, stats
        assert stats["counts"]["collective-permute"] == plan.n_steps, (
            q.delta_mode, stats)
        perms = [l for l in txt.splitlines() if "collective-permute(" in l
                 and "-done(" not in l]
        f32 = [l for l in perms if "f32[" in l.split("=", 1)[1][:24]]
        assert not f32, "f32 wire collective leaked: " + f32[0]
        print("ONE_PERMUTE_OK", q.delta_mode,
              stats["counts"]["collective-permute"])
    """)
    assert out.count("ONE_PERMUTE_OK") == 2


def test_planar_wire_kernels_in_sparse_body():
    """The Pallas quantize_pack wire (interpret mode on CPU) flows through
    the same sparse body and matches the dense reference for eq7."""
    out = run_sub(_PRELUDE + """
    sched = TopologySchedule.edge_sample(ring_graph(M), 0.5)
    q = QuantConfig(bits=8, stochastic=False, delta_mode="eq7")
    mx_p = make_mixer(sched, MixerConfig(impl="sparse", quant=q,
                                         wire="planar"),
                      mesh=mesh, client_axes=("clients",))
    mx_d = make_mixer(sched, MixerConfig(impl="dense", quant=q))
    a, _ = jax.jit(mx_p)({"w": x}, {"w": z}, jax.random.PRNGKey(7), 1)
    b, _ = jax.jit(mx_d)({"w": x}, {"w": z}, jax.random.PRNGKey(7), 1)
    err = float(jnp.max(jnp.abs(a["w"] - b["w"])))
    assert err < 1e-5, err
    print("PLANAR_OK", err)
    """)
    assert "PLANAR_OK" in out


def test_async_sparse_zero_delay_bit_identical_to_sync_sparse():
    """The async engine's sparse lowering: under a constant speed model
    the event step reproduces the synchronous sparse round step — BIT FOR
    BIT in fp32, and to float rounding (~1 ulp/round) for stochastic q8:
    the quantized flat-wire body compiles inside two different XLA
    modules whose fusion/vectorization choices can round the fused
    accumulation differently (the PRNG chain, wire words, and weights
    are identical — asserted elsewhere). A straggler run stays equivalent
    to the dense async reference."""
    out = run_sub(_PRELUDE + """
    from repro.core import (AsyncConfig, DFedAvgMConfig, SpeedModel,
                            init_async_state, init_round_state,
                            make_round_step)
    loss_fn = lambda p, b, r: 0.5 * jnp.sum((p["w"] - b["c"]) ** 2)
    batches = {"c": jnp.broadcast_to(x[:, None], (M, 4, D))}
    sched = TopologySchedule.edge_sample(ring_graph(M), 0.6)
    acfg = AsyncConfig(speed=SpeedModel.constant())
    for q in (None, QuantConfig(bits=8, stochastic=True)):
        cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4, quant=q,
                             mixer_impl="sparse")
        ss = jax.jit(make_round_step(loss_fn, cfg, sched, mesh=mesh,
                                     client_axes=("clients",)))
        sa = jax.jit(make_round_step(loss_fn, cfg, sched, mesh=mesh,
                                     client_axes=("clients",),
                                     async_cfg=acfg))
        s1 = init_round_state({"w": jnp.zeros((M, D))},
                              jax.random.PRNGKey(7))
        s2 = init_async_state({"w": jnp.zeros((M, D))},
                              jax.random.PRNGKey(7), acfg.speed)
        for _ in range(3):
            s1, _ = ss(s1, batches)
            s2, _ = sa(s2, batches)
        if q is None:
            assert np.array_equal(np.asarray(s1.params["w"]),
                                  np.asarray(s2.params["w"]))
        else:
            err = float(np.max(np.abs(np.asarray(s1.params["w"])
                                      - np.asarray(s2.params["w"]))))
            assert err < 1e-6, err
        print("ASYNC_SPARSE_OK", "q8" if q else "fp32")
    # stragglers: sparse and dense async agree (same W_eff, other backend)
    acfg2 = AsyncConfig(speed=SpeedModel.straggler(factor=4.0),
                        max_staleness=6)
    cfg_s = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4,
                           mixer_impl="sparse")
    cfg_d = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4,
                           mixer_impl="dense")
    sa = jax.jit(make_round_step(loss_fn, cfg_s, sched, mesh=mesh,
                                 client_axes=("clients",), async_cfg=acfg2))
    sd = jax.jit(make_round_step(loss_fn, cfg_d, sched, async_cfg=acfg2))
    s1 = init_async_state({"w": jnp.zeros((M, D))}, jax.random.PRNGKey(3),
                          acfg2.speed)
    s2 = init_async_state({"w": jnp.zeros((M, D))}, jax.random.PRNGKey(3),
                          acfg2.speed)
    for _ in range(10):
        s1, m1 = sa(s1, batches)
        s2, m2 = sd(s2, batches)
    err = float(np.max(np.abs(np.asarray(s1.params["w"])
                              - np.asarray(s2.params["w"]))))
    assert err < 1e-5, err
    assert float(m1["live_edges"]) == float(m2["live_edges"])
    print("ASYNC_STRAGGLER_OK", err)
    """)
    assert out.count("ASYNC_SPARSE_OK") == 2
    assert "ASYNC_STRAGGLER_OK" in out


def test_cycle_switches_per_member_plans():
    """Satellite: a cycle lowers to lax.switch over per-member plans —
    each round runs only its member's ppermutes (the HLO carries a
    conditional), and results still match the dense reference."""
    out = run_sub(_PRELUDE + """
    from repro.core.topology import Graph
    def chain_from_order(order):
        adj = np.zeros((M, M), bool)
        for a, b in zip(order[:-1], order[1:]):
            adj[a, b] = adj[b, a] = True
        return Graph(adj)
    # edge-disjoint members: the union plan would move BOTH wires per round
    cyc = TopologySchedule.cycle(
        [MixingSpec.dense(chain_from_order([0, 1, 2, 3, 4, 5, 6, 7])),
         MixingSpec.dense(chain_from_order([1, 3, 0, 5, 2, 7, 4, 6]))])
    for q in (None, QuantConfig(bits=8, stochastic=True)):
        mx_s = make_mixer(cyc, MixerConfig(impl="sparse", quant=q),
                          mesh=mesh, client_axes=("clients",))
        mx_d = make_mixer(cyc, MixerConfig(impl="dense", quant=q))
        for t in range(4):
            key = jax.random.PRNGKey(11 * t)
            a, _ = jax.jit(mx_s)({"w": x}, {"w": z}, key, t)
            b, _ = jax.jit(mx_d)({"w": x}, {"w": z}, key, t)
            err = float(jnp.max(jnp.abs(a["w"] - b["w"])))
            assert err < 1e-5, (q, t, err)
        print("CYCLE_EQ_OK", "q8" if q else "fp32")
    mx = make_mixer(cyc, MixerConfig(impl="sparse"), mesh=mesh,
                    client_axes=("clients",))
    txt = jax.jit(mx).lower({"w": x}, {"w": z}, jax.random.PRNGKey(0),
                            0).compile().as_text()
    assert "conditional" in txt, "cycle did not lower to a branch switch"
    print("CYCLE_SWITCH_OK")
    """)
    assert out.count("CYCLE_EQ_OK") == 2
    assert "CYCLE_SWITCH_OK" in out


def test_stateful_walk_sparse_matches_dense():
    """Satellite: the in-graph random-walk token drives the sparse backend
    identically to the dense reference (token state advances in lockstep)."""
    out = run_sub(_PRELUDE + """
    from repro.core import (DFedAvgMConfig, init_round_state,
                            make_round_step)
    sw = TopologySchedule.random_walk(ring_graph(M), stateful=True)
    loss_fn = lambda p, b, r: 0.5 * jnp.sum((p["w"] - b["c"]) ** 2)
    batches = {"c": jnp.broadcast_to(x[:, None], (M, 4, D))}
    def run(impl, msh):
        cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4,
                             mixer_impl=impl)
        step = jax.jit(make_round_step(loss_fn, cfg, sw, mesh=msh,
                                       client_axes=("clients",) if msh
                                       else ()))
        st = init_round_state({"w": jnp.zeros((M, D))},
                              jax.random.PRNGKey(5), token=sw.init_token())
        for _ in range(5):
            st, mt = step(st, batches)
        return np.asarray(st.params["w"]), int(st.token)
    w_d, tok_d = run("dense", None)
    w_s, tok_s = run("sparse", mesh)
    assert tok_d == tok_s
    assert np.array_equal(w_d, w_s)
    print("STATEFUL_WALK_OK", tok_s)
    """)
    assert "STATEFUL_WALK_OK" in out


def test_block_sharded_matches_dense_and_reference():
    """The block-sharding tentpole: m=32 clients over 8 shards (m_local=4)
    — the sparse backend now runs with FEWER devices than clients. For
    {constant, edge-sampled, cycle} x {fp32, q8 det, q8 stoch}: block-
    sharded sparse == dense einsum, and == the mesh-free
    ``execute_plan_reference`` (the flat-wire spec) on a pre-sampled
    event. Wire words/scales are bit-identical by construction (batched
    elementwise encode); the fused float output is a few-ulp match."""
    out = run_sub("""
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import (MixerConfig, MixingSpec, QuantConfig,
                            TopologySchedule, execute_plan_reference,
                            make_mixer)
    from repro.core.mixing import make_event_mixer
    from repro.core.topology import erdos_renyi_graph
    M = 32
    mesh = Mesh(np.array(jax.devices()[:8]), ("clients",))
    xt = {"w": jax.random.normal(jax.random.PRNGKey(0), (M, 33)),
          "b": jax.random.normal(jax.random.PRNGKey(4), (M, 3, 2))}
    zt = {"w": jax.random.normal(jax.random.PRNGKey(1), (M, 33)),
          "b": jax.random.normal(jax.random.PRNGKey(5), (M, 3, 2))}
    ring = MixingSpec.ring(M, self_weight=0.5)
    er = erdos_renyi_graph(M, 0.2, seed=3)
    scheds = [TopologySchedule.constant(ring),
              TopologySchedule.edge_sample(er, 0.6),
              TopologySchedule.cycle([ring, MixingSpec.torus(4, M // 4)])]
    quants = [None,
              QuantConfig(bits=8, stochastic=False, delta_mode="eq7"),
              QuantConfig(bits=8, stochastic=True, delta_mode="lemma5")]
    for sched in scheds:
        for q in quants:
            mx_s = make_mixer(sched, MixerConfig(impl="sparse", quant=q),
                              mesh=mesh, client_axes=("clients",))
            mx_d = make_mixer(sched, MixerConfig(impl="dense", quant=q))
            for t in range(3):
                key = jax.random.PRNGKey(10 * t + 3)
                a, act_a = jax.jit(mx_s)(xt, zt, key, t)
                b, act_b = jax.jit(mx_d)(xt, zt, key, t)
                err = max(float(jnp.max(jnp.abs(a[k] - b[k]))) for k in xt)
                assert err < 1e-5, (sched.name, q, t, err)
                assert np.array_equal(np.asarray(act_a), np.asarray(act_b))
        print("BLOCK_KIND_OK", sched.name)
    # flat-wire spec parity on a pre-sampled event (non-cycle kinds own
    # a single union-support plan the reference can execute)
    sched = scheds[1]
    plan = sched.gossip_plan()
    W_t, active, key_q = jax.jit(sched.round_event)(jax.random.PRNGKey(37), 1)
    for q in quants:
        ref = jax.jit(lambda x, z, W, a, k, q=q: execute_plan_reference(
            plan, W, z, x=x, quant=q, key=k))(xt, zt, W_t, active, key_q)
        ev = make_event_mixer(M, quant=q, mesh=mesh,
                              client_axes=("clients",), plan=plan,
                              gate=False)
        got = jax.jit(ev)(xt, zt, W_t, active, key_q)
        err = max(float(jnp.max(jnp.abs(got[k] - ref[k]))) for k in xt)
        assert err < 1e-5, (q, err)
    print("BLOCK_REF_OK")
    """, timeout=1200)
    assert out.count("BLOCK_KIND_OK") == 3
    assert "BLOCK_REF_OK" in out


def test_block_ring_hlo_moves_boundary_lanes_only():
    """The locality claim on the compiled HLO: a contiguous-blocked ring
    (m=32, 8 shards) ships exactly ONE boundary lane per direction per
    shard — 2 ppermutes of a [1, ...] buffer, O(n_shards *
    boundary_degree) wire bytes, independent of m_local — while dense
    moves the O(m) stacked axis. Quantized, the boundary lane is a
    single u32 stream row."""
    out = run_sub("""
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import (MixerConfig, MixingSpec, QuantConfig,
                            TopologySchedule, make_mixer)
    from repro.launch.hlo_stats import collect_collectives
    M, D = 32, 1024
    mesh = Mesh(np.array(jax.devices()[:8]), ("clients",))
    sh = NamedSharding(mesh, P("clients", None))
    x = jax.device_put(jax.random.normal(jax.random.PRNGKey(0), (M, D)), sh)
    z = jax.device_put(jax.random.normal(jax.random.PRNGKey(1), (M, D)), sh)
    sched = TopologySchedule.constant(MixingSpec.ring(M, self_weight=0.5))
    bp = sched.gossip_plan().block_plan(8)
    assert bp.num_collectives == 2 and bp.num_wire_lane_slots == 16
    wire = {}
    for impl in ("dense", "sparse"):
        mx = make_mixer(sched, MixerConfig(impl=impl),
                        mesh=mesh if impl == "sparse" else None,
                        client_axes=("clients",))
        fn = jax.jit(lambda a, b, k, t: mx({"w": a}, {"w": b}, k, t)[0]["w"])
        txt = fn.lower(x, z, jax.random.PRNGKey(0), 0).compile().as_text()
        wire[impl] = collect_collectives(txt).as_dict()
    sp, dn = wire["sparse"], wire["dense"]
    assert set(sp["by_kind"]) == {"collective-permute"}, sp
    assert sp["counts"]["collective-permute"] == 2, sp
    # one f32 boundary lane per direction: 2 * D * 4 bytes, NOT O(m)
    assert sp["wire_bytes"] == 2 * D * 4, sp
    assert sp["wire_bytes"] < dn["wire_bytes"] / 8, (sp, dn)
    # quantized: the boundary lane is one u32 stream row per direction
    q = QuantConfig(bits=8, stochastic=False, delta_mode="eq7")
    mx = make_mixer(sched, MixerConfig(impl="sparse", quant=q),
                    mesh=mesh, client_axes=("clients",))
    fn = jax.jit(lambda a, b, k, t: mx({"w": a}, {"w": b}, k, t)[0]["w"])
    txt = fn.lower(x, z, jax.random.PRNGKey(0), 0).compile().as_text()
    stats = collect_collectives(txt).as_dict()
    assert set(stats["counts"]) == {"collective-permute"}, stats
    assert stats["counts"]["collective-permute"] == 2, stats
    perms = [l for l in txt.splitlines() if "collective-permute(" in l
             and "-done(" not in l]
    assert all("u32[1," in l.split("=", 1)[1][:24] for l in perms), perms[0]
    print("BLOCK_HLO_OK", stats["wire_bytes"], dn["wire_bytes"])
    """)
    assert "BLOCK_HLO_OK" in out


def test_round_step_sparse_matches_dense_end_to_end():
    """Full DFedAvgM rounds (local SGD + scheduled gossip) agree between
    backends, and inactive clients still hold params exactly.

    Tolerances: the backends are independently compiled modules, so the
    local-SGD arithmetic picks up ~1-ulp FMA-contraction differences,
    and a 1-ulp pre-quant delta can flip a DETERMINISTIC quantizer
    decision at a grid knife edge — bounded at ONE quantizer step per
    affected element (the documented cross-module caveat; the wire's
    bit-identity for same inputs is pinned by the mixer-level tests).
    Hence: a loose per-element cap of a few quantizer steps, plus a
    strict cap on HOW MANY elements may sit off the FMA-level floor —
    knife edges are rare, codec corruption is not."""
    out = run_sub(_PRELUDE + """
    from repro.core import (DFedAvgMConfig, init_round_state,
                            make_round_step)
    sched = TopologySchedule.partial(ring_graph(M), 0.5)
    loss_fn = lambda p, b, r: 0.5 * jnp.sum((p["w"] - b["c"]) ** 2)
    batches = {"c": jnp.broadcast_to(x[:, None], (M, 4, D))}
    def run(impl, msh):
        cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4,
                             quant=QuantConfig(bits=8, stochastic=False),
                             mixer_impl=impl)
        step = jax.jit(make_round_step(loss_fn, cfg, sched, mesh=msh,
                                       client_axes=("clients",) if msh
                                       else ()))
        st = init_round_state({"w": jnp.zeros((M, D))},
                              jax.random.PRNGKey(7))
        for _ in range(4):
            st, mt = step(st, batches)
        return np.asarray(st.params["w"]), float(mt["active_frac"])
    w_d, af_d = run("dense", None)
    w_s, af_s = run("sparse", mesh)
    assert af_d == af_s
    diff = np.abs(w_d - w_s)
    err = float(diff.max())
    assert err < 1e-2, err
    knife_frac = float((diff > 1e-4).mean())
    assert knife_frac < 0.05, (knife_frac, err)
    print("ROUNDS_OK", err, knife_frac)
    """)
    assert "ROUNDS_OK" in out


def test_unified_executor_one_permute_per_step_m_local_1():
    """PR 9 deleted the dedicated one-client-per-shard executor bodies:
    the block realization is the ONE sparse executor, and at
    ``m_local == 1`` it must still compile to the historical
    one-WIRE-permute-per-plan-step program for every legacy plan family
    — static ring, static torus, and matching-decomposed irregular
    graphs — fp32 and quantized. Payload-sized permutes only: XLA's
    SPMD partitioner may additionally shard the threefry key split into
    a few word-sized u32 collectives, which carry no model data (their
    size is pinned tiny here). No all-gather, no f32 wire when
    quantized."""
    out = run_sub(_PRELUDE + """
    from repro.core.wire_layout import WireLayout
    from repro.launch.hlo_stats import collect_collectives
    def wire_permutes(txt, min_bytes):
        sizes = [b for kind, b in collect_collectives(txt).per_op
                 if kind == "collective-permute"]
        return ([b for b in sizes if b >= min_bytes],
                [b for b in sizes if b < min_bytes])
    lay = WireLayout.for_tree({"w": x[0]}, bits=8)
    # fp32: one f32 row; q8 lemma5: the u32 stream of packed words,
    # per-leaf scales and the f32 replica buffer.
    wire_bytes = {None: 4 * D,
                  "q8": 4 * (lay.total_words + lay.n_leaves
                             + lay.per * lay.total_words)}
    specs = [MixingSpec.ring(M, self_weight=0.5),
             MixingSpec.torus(2, M // 2),
             MixingSpec.dense(erdos_renyi_graph(M, 0.5, seed=3))]
    for spec in specs:
        plan = spec.gossip_plan()
        for q in (None, QuantConfig(bits=8, stochastic=True,
                                    delta_mode="lemma5")):
            mx = make_mixer(spec, MixerConfig(impl="sparse", quant=q),
                            mesh=mesh, client_axes=("clients",))
            txt = jax.jit(mx).lower({"w": x}, {"w": z},
                                    jax.random.PRNGKey(0),
                                    0).compile().as_text()
            assert "all-gather" not in txt, spec.kind
            wires, small = wire_permutes(txt, min_bytes=4 * D)
            assert len(wires) == plan.n_steps, \\
                (spec.kind, q and q.delta_mode, wires, small)
            # key-split artifacts stay word-sized, far below the payload
            assert all(b < 4 * D for b in small), (spec.kind, small)
            want = wire_bytes["q8" if q is not None else None]
            assert all(b == want for b in wires), (spec.kind, wires, want)
            print("UNIFIED_OK", spec.kind, plan.n_steps,
                  "q8" if q else "fp32")
    """)
    assert out.count("UNIFIED_OK") == 6


def test_placed_mesh_training_bitwise_equal_to_unplaced():
    """The tentpole's correctness claim ON THE MESH: full quantized
    stochastic DFedAvgM rounds with a partition placement produce
    BITWISE identical per-client parameters to the unplaced run (lane
    outputs land permuted; gather through the perm to compare), and the
    placed round step reports the placed boundary-lane telemetry."""
    out = run_sub(_PRELUDE + """
    from repro.core import (DFedAvgMConfig, compute_placement,
                            init_round_state, make_round_step)
    M2 = 16
    g = erdos_renyi_graph(M2, 0.35, seed=4)
    sched = TopologySchedule.partial(g, 0.6)
    pl = compute_placement(g, 8)
    loss_fn = lambda p, b, r: 0.5 * jnp.sum((p["w"] - b["c"]) ** 2)
    cs = jax.random.normal(jax.random.PRNGKey(3), (M2, D))
    batches = {"c": jnp.broadcast_to(cs[:, None], (M2, 4, D))}
    cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4,
                         quant=QuantConfig(bits=8, stochastic=True,
                                           delta_mode="lemma5"),
                         mixer_impl="sparse")
    def run(placement):
        perm = np.arange(M2) if placement is None else placement.perm
        step = jax.jit(make_round_step(
            loss_fn, cfg, sched, mesh=mesh, client_axes=("clients",),
            placement=placement, with_telemetry=True))
        st = init_round_state({"w": jnp.zeros((M2, D))[perm]},
                              jax.random.PRNGKey(7))
        b = {"c": batches["c"]}
        for _ in range(3):
            st, mt = step(st, b)
        w = np.asarray(st.params["w"])
        inv = np.empty(M2, np.int64); inv[perm] = np.arange(M2)
        tel = mt["telemetry"]
        return w[inv], float(mt["loss"]), tel.placement_boundary_lanes
    w0, l0, _ = run(None)
    w1, l1, lanes = run(pl)
    assert l0 == l1, (l0, l1)
    assert np.array_equal(w0, w1), float(np.max(np.abs(w0 - w1)))
    sp = sched.support_graph() if hasattr(sched, "support_graph") else g
    plan = sched.gossip_plan()
    expect = plan.placed(pl).block_plan(8).num_wire_lane_slots
    assert float(lanes) == float(expect), (float(lanes), expect)
    print("PLACED_BITWISE_OK", float(lanes))
    """)
    assert "PLACED_BITWISE_OK" in out
