"""GossipPlan IR invariants + backend-equivalence on the mesh-free
reference executor, for every topology this repo can express.

Deliberately hypothesis-free in its core (like test_schedule.py) so the
plan pipeline always has coverage in a bare environment; a guarded
hypothesis sweep over random graphs rides along at the bottom. The
shard_map realization of the same plans is exercised on a real CPU mesh
in test_sparse_backend_mesh.py (subprocess, 8 host devices).
"""
import hypothesis
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (MixerConfig, MixingSpec, QuantConfig,
                        TopologySchedule, execute_plan_reference, make_mixer,
                        mix_dense, plan_round_bits, round_comm_bits,
                        schedule_round_bits)
from repro.core.gossip_plan import (GossipPlan, matching_steps, ring_steps,
                                    torus_steps)
from repro.core.mixing import _mix_dense_quantized
from repro.core.topology import erdos_renyi_graph, ring_graph, star_graph

M, D = 8, 13


def all_schedules(m=M):
    ring = MixingSpec.ring(m, self_weight=0.5)
    er = erdos_renyi_graph(m, 0.5, seed=3)
    return [
        TopologySchedule.constant(ring),
        TopologySchedule.edge_sample(er, p_edge=0.6),
        TopologySchedule.partial(ring_graph(m), p_active=0.5),
        TopologySchedule.random_walk(ring_graph(m), horizon=32, seed=1),
        TopologySchedule.cycle([ring, MixingSpec.torus(2, m // 2)]),
    ]


# ---------------------------------------------------------------------------
# IR invariants: permutations, exact edge coverage, weight reconstruction
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", [
    MixingSpec.ring(2), MixingSpec.ring(M), MixingSpec.ring(7),
    MixingSpec.torus(2, 4), MixingSpec.torus(4, 4), MixingSpec.torus(2, 2),
    MixingSpec.dense(erdos_renyi_graph(M, 0.5, seed=3)),
    MixingSpec.dense(star_graph(M)), MixingSpec.complete(6),
], ids=lambda s: s.graph.name)
def test_static_plan_reconstructs_w_exactly(spec):
    """Every step a permutation, every directed edge covered exactly once,
    and the gathered weights rebuild W bit-for-bit."""
    plan = spec.gossip_plan()
    ref = np.arange(spec.m)
    for k in range(plan.n_steps):
        assert np.array_equal(np.sort(plan.src[k]), ref)
    assert plan.num_directed_wire_edges == spec.graph.num_directed_edges()
    assert plan.max_degree == int(spec.graph.degrees().max())
    np.testing.assert_array_equal(plan.as_matrix(), spec.W)


def test_ring_and_torus_plans_are_minimal():
    """Ring = 2 shift steps (1 at m=2); torus = one step per distinct
    neighbor direction — the O(degree) collective schedule."""
    assert ring_steps(M).shape == (2, M)
    assert ring_steps(2).shape == (1, 2)
    assert torus_steps(4, 4).shape == (4, 16)
    assert torus_steps(2, 4).shape == (3, 8)   # rows==2: +-1 coincide
    assert torus_steps(2, 2).shape == (2, 4)


def test_matching_steps_bounded_by_vizing_like_budget():
    g = erdos_renyi_graph(M, 0.6, seed=7)
    src = matching_steps(g.adj)
    dmax = int(g.degrees().max())
    assert src.shape[0] <= 2 * dmax - 1
    # involutions: applying twice is the identity
    for k in range(src.shape[0]):
        assert np.array_equal(src[k][src[k]], np.arange(M))


def test_plan_rejects_non_permutation_and_double_cover():
    with pytest.raises(ValueError, match="permutation"):
        GossipPlan(m=4, src=np.array([[0, 0, 1, 2]], np.int32))
    from repro.core.gossip_plan import _check_exact_cover
    g = ring_graph(4)
    dup = np.stack([ring_steps(4)[0], ring_steps(4)[0]])  # left edge twice
    with pytest.raises(ValueError, match="exactly once"):
        _check_exact_cover(dup, g.adj)


def test_schedule_support_covers_every_sampled_round():
    """W_t may only place weight where the compiled plan has an edge —
    that's what makes the static ppermute schedule sufficient."""
    for sched in all_schedules():
        plan = sched.gossip_plan()
        support = sched.support_graph().adj
        for t in range(5):
            W, _ = sched.sample_w(jax.random.PRNGKey(t), t)
            W = np.asarray(W)
            off = ~np.eye(M, dtype=bool)
            assert not ((W != 0) & off & ~support).any(), sched.name
        # gathered weights on a sampled round rebuild W_t exactly
        W, _ = sched.sample_w(jax.random.PRNGKey(9), 2)
        w_self, w_steps = plan.gather_weights(W)
        rebuilt = np.zeros((M, M), np.float32)
        rebuilt[np.arange(M), np.arange(M)] = np.asarray(w_self)
        for k in range(plan.n_steps):
            rows = plan.src[k] != np.arange(M)
            rebuilt[np.nonzero(rows)[0], plan.src[k][rows]] += \
                np.asarray(w_steps)[k][rows]
        np.testing.assert_allclose(rebuilt, np.asarray(W), atol=1e-7)


# ---------------------------------------------------------------------------
# Backend equivalence (mesh-free executor): every kind, several rounds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sched", all_schedules(), ids=lambda s: s.name)
def test_plan_execution_matches_dense_all_kinds(sched):
    plan = sched.gossip_plan()
    z = {"w": jax.random.normal(jax.random.PRNGKey(0), (M, D)),
         "b": jax.random.normal(jax.random.PRNGKey(1), (M, 3, 2))}
    for t in range(4):
        W, _ = sched.sample_w(jax.random.PRNGKey(100 + t), t)
        out = execute_plan_reference(plan, W, z)
        ref = mix_dense(W, z)
        for k in z:
            np.testing.assert_allclose(np.asarray(out[k]),
                                       np.asarray(ref[k]), rtol=1e-5,
                                       atol=1e-6)


# ---------------------------------------------------------------------------
# MixerConfig validation + quantized-torus fallback (satellites)
# ---------------------------------------------------------------------------

def test_mixer_config_validates_impl_and_wire():
    for impl in ("auto", "dense", "ring", "torus", "sparse"):
        MixerConfig(impl=impl)
    with pytest.raises(ValueError, match="'sparse'"):
        MixerConfig(impl="bogus")      # error lists the allowed impls
    with pytest.raises(ValueError, match="allowed"):
        MixerConfig(wire="zigzag")


def test_quantized_torus_without_mesh_warns_and_matches_dense():
    """The old code silently fell back to the dense reference; now the
    fallback WARNS (and with a usable mesh it routes through the sparse
    backend — asserted in test_sparse_backend_mesh.py)."""
    spec = MixingSpec.torus(2, 4)
    quant = QuantConfig(bits=8, stochastic=False)
    with pytest.warns(UserWarning, match="DENSE reference"):
        mixer = make_mixer(spec, MixerConfig(impl="torus", quant=quant),
                           mesh=None)
    x = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, D))}
    z = {"w": jax.random.normal(jax.random.PRNGKey(1), (8, D))}
    key = jax.random.PRNGKey(2)
    out = mixer(x, z, key)
    ref = _mix_dense_quantized(spec.W, x, z, quant, key)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(ref["w"]))


def test_auto_resolution_prefers_sparse_when_mesh_fits():
    """auto -> sparse for any bounded-degree topology on a fitting mesh
    (ring/torus keep their named plan instances; complete graphs keep
    the all-gather, which is optimal there)."""
    import types
    mesh8 = types.SimpleNamespace(axis_names=("clients",),
                                  devices=np.zeros((M,)))
    cfg = MixerConfig(impl="auto")
    er = MixingSpec.dense(erdos_renyi_graph(M, 0.5, seed=3))
    sched = TopologySchedule.edge_sample(ring_graph(M), 0.5)
    assert cfg.resolved_impl(er, mesh8) == "sparse"
    assert cfg.resolved_impl(sched, mesh8) == "sparse"
    assert cfg.resolved_impl(MixingSpec.ring(M), mesh8) == "ring"
    assert cfg.resolved_impl(MixingSpec.torus(2, 4), mesh8) == "torus"
    assert cfg.resolved_impl(MixingSpec.complete(M), mesh8) == "dense"
    # no usable mesh -> dense reference, always
    for spec in (er, sched, MixingSpec.ring(M)):
        assert cfg.resolved_impl(spec, None) == "dense"


def test_planar_wire_supports_every_quant_mode():
    """The flat wire-buffer path runs EVERY quant mode through the Pallas
    buffer kernels — the old eq7-only planar restriction (which used to
    warn and silently fall back to the per-leaf sequential codec) is
    gone."""
    import types
    import warnings as warnings_mod
    from repro.core.mixing import _make_sparse_exec
    mesh8 = types.SimpleNamespace(axis_names=("clients",),
                                  devices=np.zeros((M,)))
    plan = MixingSpec.ring(M).gossip_plan()
    for q in (QuantConfig(bits=8, delta_mode="lemma5"),
              QuantConfig(bits=8, delta_mode="eq7"),
              QuantConfig(bits=4, scale_mode="fixed", s=1e-3)):
        with warnings_mod.catch_warnings():
            warnings_mod.simplefilter("error")
            ex = _make_sparse_exec(plan, mesh8, ("clients",), None, q,
                                   wire="planar")
        assert callable(ex)


def test_unquantized_sparse_impls_require_mesh():
    with pytest.raises(ValueError, match="one client block per shard"):
        make_mixer(MixingSpec.ring(M), MixerConfig(impl="ring"), mesh=None)
    with pytest.raises(ValueError, match="one client block per shard"):
        make_mixer(MixingSpec.ring(M), MixerConfig(impl="sparse"), mesh=None)


# ---------------------------------------------------------------------------
# Block-sharded compilation: m_local clients per shard
# ---------------------------------------------------------------------------

def _simulate_block_step(bp, k, rows):
    """Numpy emulation of one block-plan step on per-client payload rows
    [m, n]: intra gathers + per-sub-step ppermute/scatter — exactly what
    the shard_map body executes."""
    m_local, n_shards = bp.m_local, bp.n_shards
    blocks = rows.reshape(n_shards, m_local, -1)
    recv = np.stack([blocks[s][bp.intra_src[k, s]]
                     for s in range(n_shards)])
    for sub in bp.substeps[k]:
        sent = np.stack([blocks[s][sub.send_lanes[s]]
                         for s in range(n_shards)])    # [S, width, n]
        got = np.zeros_like(sent)                       # ppermute zero-fill
        for s_src, s_dst in sub.pairs:
            got[s_dst] = sent[s_src]
        for s in range(n_shards):
            for b in range(sub.width):
                if sub.recv_lanes[s, b] < m_local:      # drop-mode scatter
                    recv[s, sub.recv_lanes[s, b]] = got[s, b]
    return recv.reshape(rows.shape[0], -1)


@pytest.mark.parametrize("spec,n_shards", [
    (MixingSpec.ring(M), 4),
    (MixingSpec.ring(M), 2),
    (MixingSpec.torus(4, 4), 4),
    (MixingSpec.dense(erdos_renyi_graph(12, 0.5, seed=3)), 3),
    (MixingSpec.dense(star_graph(M)), 4),
], ids=lambda v: getattr(getattr(v, "graph", None), "name", v))
def test_block_plan_realizes_every_step(spec, n_shards):
    """The block compilation (intra lane gathers + boundary ppermute
    sub-steps) reproduces each step's receive ``rows[src[k]]`` at every
    NON-IDLE lane, for shift plans and matchings alike."""
    plan = spec.gossip_plan()
    bp = plan.block_plan(n_shards)
    assert bp.m_local * n_shards == spec.m
    rng = np.random.default_rng(0)
    rows = rng.normal(size=(spec.m, 7)).astype(np.float32)
    for k in range(plan.n_steps):
        got = _simulate_block_step(bp, k, rows)
        want = rows[plan.src[k]]
        live = plan.src[k] != np.arange(spec.m)
        np.testing.assert_array_equal(got[live], want[live])
        # every sub-step is a partial shard permutation
        for sub in bp.substeps[k]:
            srcs = [p[0] for p in sub.pairs]
            dsts = [p[1] for p in sub.pairs]
            assert len(set(srcs)) == len(srcs)
            assert len(set(dsts)) == len(dsts)


def test_block_plan_ring_wire_is_boundary_only():
    """Contiguous-blocked ring: ONE boundary lane per direction per shard
    — O(n_shards * boundary_degree) lane slots, matching the graph's
    boundary-edge count, with zero wire for the intra-block edges."""
    for m, n_shards in ((M, 4), (32, 8), (64, 8)):
        spec = MixingSpec.ring(m, self_weight=0.5)
        bp = spec.gossip_plan().block_plan(n_shards)
        assert bp.num_collectives == 2          # one ppermute per shift
        assert bp.num_wire_lane_slots == 2 * n_shards
        assert bp.num_wire_lane_slots == \
            spec.graph.block_boundary_edges(m // n_shards)
        for subs in bp.substeps:
            assert all(sub.width == 1 for sub in subs)
    # degenerate single-shard mesh: everything is intra, zero collectives
    bp1 = MixingSpec.ring(M).gossip_plan().block_plan(1)
    assert bp1.num_collectives == 0 and bp1.num_wire_lane_slots == 0


def test_block_plan_rejects_non_dividing_shards():
    plan = MixingSpec.ring(M).gossip_plan()
    with pytest.raises(ValueError, match="block"):
        plan.block_plan(3)


def test_auto_resolution_accepts_block_meshes():
    """auto -> sparse when the mesh's shard count DIVIDES m (each shard a
    block of m_local clients), not only when it equals m."""
    import types
    mesh4 = types.SimpleNamespace(axis_names=("clients",),
                                  devices=np.zeros((4,)))
    mesh3 = types.SimpleNamespace(axis_names=("clients",),
                                  devices=np.zeros((3,)))
    cfg = MixerConfig(impl="auto")
    sched = TopologySchedule.edge_sample(ring_graph(M), 0.5)
    assert cfg.resolved_impl(sched, mesh4) == "sparse"
    assert cfg.resolved_impl(MixingSpec.ring(M), mesh4) == "ring"
    # 3 shards don't divide m=8: unusable, dense
    assert cfg.resolved_impl(sched, mesh3) == "dense"


def test_plan_round_bits_block_sharded_bills_boundary_lanes():
    d = 1000
    ring = MixingSpec.ring(32, self_weight=0.5)
    plan = ring.gossip_plan()
    q = QuantConfig(bits=8)
    # one-client-per-shard: every directed edge (2m); blocked over 8
    # shards: only the 2*n_shards boundary lanes
    assert plan_round_bits(plan, d, q) == (32 + 8 * d) * 2 * 32
    assert plan_round_bits(plan, d, q, clients_per_shard=4) \
        == (32 + 8 * d) * 2 * 8


# ---------------------------------------------------------------------------
# Realized-edge billing
# ---------------------------------------------------------------------------

def test_plan_round_bits_is_a_wire_diagnostic_not_the_bill():
    d = 1000
    ring = MixingSpec.ring(M, self_weight=0.5)
    plan = ring.gossip_plan()
    assert plan_round_bits(plan, d, None) == 32 * d * 2 * M
    q = QuantConfig(bits=4)
    assert plan_round_bits(plan, d, q) == (32 + 4 * d) * 2 * M
    # lemma5 replica rows are billable on request
    q5 = QuantConfig(bits=4, delta_mode="lemma5")
    assert plan_round_bits(plan, d, q5, count_lemma5_replicas=True) \
        == (32 + 4 * d + 32 * d) * 2 * M
    # static specs: plan wire == live edges, so every view agrees
    assert round_comm_bits(ring, d, None, plan=plan) \
        == plan_round_bits(plan, d, None)
    # schedules: the LEDGER convention is the live-edge expectation for
    # BOTH backends; the plan's full masked wire stays available as a
    # diagnostic of what the sparse collective physically moves (1/p x)
    sched = TopologySchedule.edge_sample(ring_graph(M), 0.5)
    splan = sched.gossip_plan()
    assert schedule_round_bits(sched, d, None) \
        == pytest.approx(0.5 * plan_round_bits(splan, d, None))
    assert round_comm_bits(sched, d, None, plan=splan) \
        == schedule_round_bits(sched, d, None)


# ---------------------------------------------------------------------------
# Hypothesis sweep
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=25)
@given(m=st.integers(4, 12), p=st.floats(0.2, 0.9),
       seed=st.integers(0, 1000))
def test_property_random_graph_plan_equivalence(m, p, seed):
    """Any connected random graph: the plan rebuilds Metropolis W
    exactly and the plan executor matches the dense einsum."""
    try:
        g = erdos_renyi_graph(m, p, seed=seed)
    except RuntimeError:
        hypothesis.assume(False)
    spec = MixingSpec.dense(g)
    plan = spec.gossip_plan()
    np.testing.assert_array_equal(plan.as_matrix(), spec.W)
    z = {"w": jax.random.normal(jax.random.PRNGKey(seed), (m, 5))}
    out = execute_plan_reference(plan, spec.W, z)["w"]
    ref = mix_dense(spec.W, z)["w"]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
