"""Mixing-matrix / graph properties (paper §2, Definition 1)."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.topology import (Graph, MixingSpec, check_mixing_matrix,
                                 chain_graph, complete_graph,
                                 erdos_renyi_graph, max_degree_weights,
                                 metropolis_hastings, mixing_lambda,
                                 ring_graph, spectral_gap, star_graph,
                                 torus_graph)


@pytest.mark.parametrize("maker", [
    lambda m: ring_graph(m),
    lambda m: chain_graph(m),
    lambda m: complete_graph(m),
    lambda m: star_graph(m),
])
@pytest.mark.parametrize("m", [2, 3, 8, 17])
def test_graphs_connected(maker, m):
    g = maker(m)
    assert g.is_connected()
    assert g.m == m
    assert not g.adj.diagonal().any()


def test_torus():
    g = torus_graph(4, 4)
    assert g.is_connected()
    assert (g.degrees() == 4).all()


@given(st.integers(3, 24), st.floats(0.2, 0.9))
@settings(max_examples=20, deadline=None)
def test_erdos_renyi_connected(m, p):
    g = erdos_renyi_graph(m, p, seed=1)
    assert g.is_connected()


@pytest.mark.parametrize("scheme", ["metropolis", "max_degree"])
@pytest.mark.parametrize("maker,m", [
    (ring_graph, 8), (chain_graph, 5), (complete_graph, 6),
    (star_graph, 7), (lambda m: erdos_renyi_graph(m, 0.5, seed=3), 10),
])
def test_mixing_matrices_valid(scheme, maker, m):
    g = maker(m)
    spec = MixingSpec.dense(g, scheme=scheme)
    check_mixing_matrix(spec.W, g)      # Definition 1 end-to-end
    assert 0.0 < spec.lam < 1.0


def test_ring_spec_psd_option():
    s = MixingSpec.ring(8, self_weight=0.5)
    ev = np.linalg.eigvalsh(s.W)
    assert ev.min() > -1e-9             # PSD: safe for Algorithm 2 / eq. 7
    s13 = MixingSpec.ring(8)            # classic 1/3 weights: NOT PSD
    assert np.linalg.eigvalsh(s13.W).min() < -0.2


def test_complete_lambda_zero():
    s = MixingSpec.complete(9)
    assert s.lam < 1e-12                # W = 11^T/m mixes in one step


def test_spectral_gap_ordering():
    # better-connected graphs mix faster: complete < torus < ring < chain
    lam = {
        "chain": mixing_lambda(metropolis_hastings(chain_graph(16))),
        "ring": mixing_lambda(metropolis_hastings(ring_graph(16))),
        "torus": mixing_lambda(metropolis_hastings(torus_graph(4, 4))),
        "complete": mixing_lambda(metropolis_hastings(complete_graph(16))),
    }
    assert lam["complete"] < lam["torus"] < lam["ring"] < lam["chain"]


def test_lemma1_operator_bound():
    """Lemma 1: ||W^k - 11^T/m||_op <= lambda^k."""
    spec = MixingSpec.dense(ring_graph(10), scheme="metropolis")
    m = spec.m
    P = np.full((m, m), 1.0 / m)
    Wk = np.eye(m)
    for k in range(1, 25):
        Wk = Wk @ spec.W
        opnorm = np.linalg.norm(Wk - P, ord=2)
        assert opnorm <= spec.lam ** k + 1e-9, k


def test_invalid_matrices_rejected():
    g = ring_graph(4)
    W = metropolis_hastings(g)
    with pytest.raises(ValueError):
        check_mixing_matrix(W + 0.01, g)          # rows don't sum to 1
    W2 = W.copy()
    W2[0, 2] = W2[2, 0] = 0.1                     # weight on non-edge
    W2[0, 0] -= 0.1
    W2[2, 2] -= 0.1
    with pytest.raises(ValueError):
        check_mixing_matrix(W2, g)
    bad = np.eye(4)                               # disconnected (I)
    with pytest.raises(ValueError):
        check_mixing_matrix(bad, None)
