"""The placement pass: partition quality, placed-execution bitwise
equivalence, and the operational guard rails around it.

Covers the PR's acceptance matrix without needing a device mesh (the
mesh-level placed-vs-unplaced run lives in
``tests/test_sparse_backend_mesh.py``):

  * ``compute_placement`` structure: balanced blocks, deterministic,
    NEVER worse than the contiguous split — and a strict cost NO-OP on
    ring / torus graphs, whose contiguous layout is already optimal
  * the headline win, mirroring the CI bench gate: the ER(64, p=0.06)
    arm's boundary lane slots at least HALVE vs contiguous on 8 shards
  * placed plans conjugate correctly: ``as_matrix`` is
    placement-invariant, the block compiler sees the partition's blocks,
    and ``execute_plan_reference`` on a placed plan is BITWISE equal to
    the unplaced reference (outputs permuted) across fp32 / q8
    deterministic / q8 stochastic — for arbitrary permutations, not just
    the ones the partitioner emits (hypothesis sweeps random graphs x
    random perms when available)
  * ``make_client_mesh``'s dense-fallback warning fires EXACTLY once per
    (m, clients_per_shard) shape, names ``--placement`` and the actual
    shard/device mismatch, and the dense fallback still trains
  * ``tools/check_single_executor.py`` passes: ``core/mixing.py`` has
    exactly one sparse executor
"""
import os
import subprocess
import sys
import warnings

import hypothesis
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (DFedAvgMConfig, MixingSpec, QuantConfig,
                        average_params, compute_placement,
                        init_round_state, make_round_step)
from repro.core.gossip_plan import Placement, plan_from_support
from repro.core.mixing import (_mix_dense_quantized, execute_plan_reference,
                               mix_dense)
from repro.core.topology import erdos_renyi_graph, ring_graph, torus_graph

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Placement structure
# ---------------------------------------------------------------------------

def test_placement_validates_and_inverts():
    pl = Placement(perm=np.array([2, 0, 3, 1]), n_shards=2)
    np.testing.assert_array_equal(pl.inv[pl.perm], np.arange(4))
    assert pl.m == 4 and pl.m_local == 2 and not pl.is_identity
    # client c lands on shard inv[c] // m_local
    np.testing.assert_array_equal(pl.shard_of(), [0, 1, 0, 1])
    with pytest.raises(ValueError):
        Placement(perm=np.array([0, 0, 1, 2]), n_shards=2)  # not a perm
    with pytest.raises(ValueError):
        Placement(perm=np.arange(4), n_shards=3)            # 3 !| 4
    assert Placement.contiguous(8, 2).is_identity


def test_compute_placement_balanced_and_deterministic():
    g = erdos_renyi_graph(24, 0.3, seed=5)
    pl = compute_placement(g, 4)
    np.testing.assert_array_equal(np.sort(pl.perm), np.arange(24))
    counts = np.bincount(pl.shard_of(), minlength=4)
    assert (counts == 6).all(), counts
    pl2 = compute_placement(g, 4)
    np.testing.assert_array_equal(pl.perm, pl2.perm)


def test_ring_and_torus_placement_is_cost_noop():
    """Contiguous blocking is already optimal for banded topologies: the
    partitioner must return the identity (contiguous candidate wins on
    strict improvement), leaving the cut untouched."""
    for g, shards in ((ring_graph(32), 8), (torus_graph(4, 8), 8)):
        pl = compute_placement(g, shards)
        assert pl.is_identity, (g.name, pl.perm)
        cps = g.m // shards
        assert g.block_boundary_edges(cps, perm=pl) \
            == g.block_boundary_edges(cps)


def test_placement_never_worse_than_contiguous():
    for seed in range(6):
        g = erdos_renyi_graph(32, 0.2, seed=seed)
        pl = compute_placement(g, 8)
        assert g.block_boundary_edges(4, perm=pl) \
            <= g.block_boundary_edges(4), (seed, pl.perm)


def test_placement_boundary_edges_views_agree():
    g = erdos_renyi_graph(32, 0.25, seed=3)
    pl = compute_placement(g, 8)
    assert pl.boundary_edges(g.adj) == g.block_boundary_edges(4, perm=pl)


def test_er64_arm_halves_boundary_lane_slots():
    """The bench/CI gate, pinned here too: on the irregular ER arm the
    partition placement at least halves the block realization's wire
    lane slots vs the blind contiguous split (m=64, 8 shards)."""
    g = erdos_renyi_graph(64, 0.06, seed=2)
    plan = plan_from_support(g, name=g.name)
    pl = compute_placement(g, 8)
    cont = plan.block_plan(8).num_wire_lane_slots
    part = plan.block_plan(8, placement=pl).num_wire_lane_slots
    assert part <= cont / 2, (cont, part)


# ---------------------------------------------------------------------------
# Placed plans: conjugation + bitwise execution equivalence
# ---------------------------------------------------------------------------

def _rand_placement(m, n_shards, seed):
    rng = np.random.default_rng(seed)
    return Placement(perm=rng.permutation(m).astype(np.int32),
                     n_shards=n_shards)


def test_placed_plan_as_matrix_is_placement_invariant():
    g = erdos_renyi_graph(12, 0.4, seed=1)
    spec = MixingSpec.dense(g)
    plan = spec.gossip_plan()
    pl = _rand_placement(12, 4, seed=9)
    placed = plan.placed(pl)
    assert placed.name.endswith("@partition")
    np.testing.assert_array_equal(placed.lane_to_client, pl.perm)
    np.testing.assert_allclose(placed.as_matrix(), plan.as_matrix(),
                               atol=1e-12)
    with pytest.raises(ValueError):
        placed.placed(pl)               # double placement
    with pytest.raises(ValueError):
        plan.placed(_rand_placement(8, 4, seed=0))  # wrong m


QUANTS = [None,
          QuantConfig(bits=8, stochastic=False, delta_mode="eq7"),
          QuantConfig(bits=8, stochastic=True, delta_mode="lemma5")]


def _check_placed_bitwise(g, perm_seed, data_seed):
    """Placed reference output == unplaced reference output gathered
    through the perm, BIT FOR BIT, for every quant mode — and both match
    the dense reference at float tolerance."""
    m = g.m
    spec = MixingSpec.dense(g)
    plan = spec.gossip_plan()
    pl = _rand_placement(m, 4, seed=perm_seed)
    placed = plan.placed(pl)
    perm = pl.perm

    kx, kz, kq = jax.random.split(jax.random.PRNGKey(data_seed), 3)
    x = {"w": jax.random.normal(kx, (m, 17)),
         "b": jax.random.normal(kz, (m, 3, 5))}
    z = jax.tree.map(lambda l: l + 0.1 * jnp.sign(l), x)
    xp = jax.tree.map(lambda l: l[perm], x)
    zp = jax.tree.map(lambda l: l[perm], z)

    for q in QUANTS:
        if q is None:
            a = execute_plan_reference(plan, spec.W, z)
            b = execute_plan_reference(placed, spec.W, zp)
            dense = mix_dense(spec.W, z)
        else:
            a = execute_plan_reference(plan, spec.W, z, x=x, quant=q,
                                       key=kq)
            b = execute_plan_reference(placed, spec.W, zp, x=xp, quant=q,
                                       key=kq)
            dense = _mix_dense_quantized(spec.W, x, z, q, kq)
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            assert np.array_equal(np.asarray(la)[perm], np.asarray(lb)), \
                (g.name, q and q.delta_mode)
        for la, ld in zip(jax.tree.leaves(a), jax.tree.leaves(dense)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(ld),
                                       rtol=2e-5, atol=2e-5)


def test_placed_reference_bitwise_all_quant_modes():
    for seed in range(3):
        g = erdos_renyi_graph(8, 0.5, seed=seed + 10)
        _check_placed_bitwise(g, perm_seed=seed, data_seed=seed + 40)


# ---------------------------------------------------------------------------
# Hypothesis sweep
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=15)
@given(p=st.floats(0.25, 0.8), gseed=st.integers(0, 500),
       pseed=st.integers(0, 500), dseed=st.integers(0, 500))
def test_property_placed_bitwise_random_graph_and_perm(
        p, gseed, pseed, dseed):
    """Any connected random graph x any random permutation: the
    placed reference replays each client's exact arithmetic on its
    new lane (fp32 and both quantized modes, stochastic draws
    included)."""
    try:
        g = erdos_renyi_graph(8, p, seed=gseed)
    except RuntimeError:
        hypothesis.assume(False)
    _check_placed_bitwise(g, perm_seed=pseed, data_seed=dseed)


# ---------------------------------------------------------------------------
# Dense-fallback warning + training regression
# ---------------------------------------------------------------------------

def test_mesh_fallback_warns_once_names_placement_and_still_trains():
    from repro.launch.mesh import _FALLBACK_WARNED, make_client_mesh

    n_dev = len(jax.devices())
    m = 8 * n_dev                       # guaranteed too many shards
    _FALLBACK_WARNED.discard((m, 1, 1))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert make_client_mesh(m) is None
        assert make_client_mesh(m) is None          # second call: silent
    msgs = [str(x.message) for x in w
            if "make_client_mesh" in str(x.message)]
    assert len(msgs) == 1, msgs
    # names the control flags and the ACTUAL mismatch numbers
    assert "--placement" in msgs[0]
    assert f"needs {m} devices" in msgs[0]
    assert f"has {n_dev}" in msgs[0]
    assert f"{m - n_dev} short" in msgs[0]

    # the dense fallback the warning points at still trains
    M, D = 8, 6
    cs = jax.random.normal(jax.random.PRNGKey(1), (M, D))

    def loss_fn(prm, batch, rng):
        return 0.5 * jnp.sum((prm["w"] - batch["c"]) ** 2)

    batches = {"c": jnp.broadcast_to(cs[:, None], (M, 2, D))}
    step = jax.jit(make_round_step(
        loss_fn, DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=2),
        MixingSpec.ring(M), mesh=None))             # mesh=None: dense
    stt = init_round_state({"w": jnp.zeros((M, D))}, jax.random.PRNGKey(2))
    losses = []
    for _ in range(30):
        stt, mt = step(stt, batches)
        losses.append(float(mt["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    avg = average_params(stt.params)["w"]
    assert float(jnp.linalg.norm(avg - cs.mean(0))) < 0.5


# ---------------------------------------------------------------------------
# Single-executor lint
# ---------------------------------------------------------------------------

def test_single_sparse_executor_lint_passes():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "check_single_executor.py")],
        capture_output=True, text=True, timeout=60,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "_make_sparse_exec" in r.stdout


_LINT_OK = '''
import jax

def _make_sparse_exec(plan):
    def ex(x):
        s = jax.lax.pmax(x, "model")
        return jax.lax.ppermute(s, "clients", [(0, 1)])
    return ex
'''


@pytest.mark.parametrize("offence,extra", [
    ("ppermute", "def make_tail(x):\n"
                 "    return jax.lax.ppermute(x, 'clients', [(0, 1)])\n"),
    ("pmax", "def make_tail(x):\n"
             "    return jax.lax.pmax(x, 'model')\n"),
    ("second sparse executor", "def _make_tail_exec(plan):\n"
                               "    return plan\n"),
])
def test_single_sparse_executor_lint_rejects(tmp_path, offence, extra):
    """``_make_sparse_exec`` is the one scope allowed a ppermute or a
    pmax, and the one ``*_exec`` factory: a second round tail that
    carries its own wire fails the lint."""
    lint = os.path.join(REPO, "tools", "check_single_executor.py")
    ok, bad = tmp_path / "ok.py", tmp_path / "bad.py"
    ok.write_text(_LINT_OK)
    bad.write_text(_LINT_OK + "\n\n" + extra)
    r = subprocess.run([sys.executable, lint, str(ok)], capture_output=True,
                       text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    r = subprocess.run([sys.executable, lint, str(bad)], capture_output=True,
                       text=True, timeout=60)
    assert r.returncode == 1 and offence in r.stdout, r.stdout + r.stderr
