"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (DFedAvgMConfig, MixingSpec, QuantConfig,
                        average_params, consensus_distance,
                        init_round_state, make_round_step)
from repro.core.mixing import mix_dense
from repro.core.topology import metropolis_hastings, erdos_renyi_graph


@given(st.integers(3, 16), st.integers(0, 100))
@settings(max_examples=20, deadline=None)
def test_mixing_preserves_mean(m, seed):
    """INVARIANT: gossip with doubly-stochastic W preserves the client
    average exactly — the quantity the theory tracks (xbar dynamics)."""
    z = jax.random.normal(jax.random.PRNGKey(seed), (m, 9))
    g = erdos_renyi_graph(m, 0.6, seed=seed % 7)
    W = metropolis_hastings(g)
    mixed = mix_dense(W, {"w": z})["w"]
    np.testing.assert_allclose(np.asarray(mixed.mean(0)),
                               np.asarray(z.mean(0)), atol=1e-5)


@given(st.integers(3, 12), st.integers(0, 50))
@settings(max_examples=15, deadline=None)
def test_mixing_contracts_consensus(m, seed):
    """INVARIANT: ||X' - P X'|| <= lambda ||X - P X|| (Lemma 1 corollary)."""
    z = jax.random.normal(jax.random.PRNGKey(seed), (m, 5))
    spec = MixingSpec.dense(erdos_renyi_graph(m, 0.7, seed=seed % 5))
    before = float(consensus_distance({"w": z}))
    after = float(consensus_distance(mix_dense(spec.W, {"w": z})))
    assert after <= spec.lam ** 2 * before + 1e-6


@given(st.integers(0, 30))
@settings(max_examples=8, deadline=None)
def test_round_step_mean_equals_local_training_mean(seed):
    """INVARIANT (eq. 17): xbar^{t+1} = zbar^t — gossip never changes the
    average; only local training moves it."""
    m, d = 6, 8
    cs = jax.random.normal(jax.random.PRNGKey(seed), (m, d))

    def loss_fn(p, b, r):
        return 0.5 * jnp.sum((p["w"] - b["c"]) ** 2)

    batches = {"c": jnp.broadcast_to(cs[:, None], (m, 3, d))}
    step = jax.jit(make_round_step(loss_fn, DFedAvgMConfig(
        eta=0.03, theta=0.4, local_steps=3), MixingSpec.ring(m)))
    st = init_round_state(
        {"w": jax.random.normal(jax.random.PRNGKey(seed + 1), (m, d))},
        jax.random.PRNGKey(0))
    from repro.core.local_sgd import local_train
    keys = jax.random.split(jax.random.split(st.rng, 3)[0], m)
    z, _ = jax.vmap(lambda p, b, k: local_train(
        loss_fn, {"w": p}, b, k, eta=0.03, theta=0.4))(
        st.params["w"], batches, keys)
    st2, _ = step(st, batches)
    np.testing.assert_allclose(np.asarray(st2.params["w"].mean(0)),
                               np.asarray(z["w"].mean(0)), atol=1e-5)


@given(st.sampled_from([2, 4, 8, 16]), st.integers(0, 40))
@settings(max_examples=20, deadline=None)
def test_quantized_mix_error_bounded(bits, seed):
    """INVARIANT: one quantized lemma5 round deviates from the exact round
    by O(s) per coordinate."""
    m, d = 6, 32
    x = jax.random.normal(jax.random.PRNGKey(seed), (m, d))
    z = x + 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1), (m, d))
    spec = MixingSpec.ring(m)
    exact = mix_dense(spec.W, {"w": z})["w"]
    from repro.core.mixing import _mix_dense_quantized
    qc = QuantConfig(bits=bits, stochastic=False, delta_mode="lemma5")
    approx = _mix_dense_quantized(spec.W, {"w": x}, {"w": z}, qc,
                                  jax.random.PRNGKey(0))["w"]
    # s per leaf = max|delta| / qmax  (per client); deviation <= s
    s_max = float(jnp.max(jnp.abs(z - x))) / (2 ** (bits - 1) - 1)
    assert float(jnp.max(jnp.abs(approx - exact))) <= s_max * (1 + 1e-4)


@given(st.integers(2, 10))
@settings(max_examples=10, deadline=None)
def test_average_params_idempotent(m):
    t = {"a": jax.random.normal(jax.random.PRNGKey(m), (m, 4, 3))}
    avg = average_params(t)
    stacked = {"a": jnp.broadcast_to(avg["a"][None], (m, 4, 3))}
    avg2 = average_params(stacked)
    np.testing.assert_allclose(np.asarray(avg["a"]), np.asarray(avg2["a"]),
                               rtol=1e-6)
    assert float(consensus_distance(stacked)) < 1e-10
