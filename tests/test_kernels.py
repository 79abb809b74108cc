"""Pallas kernels vs pure-jnp ref oracles: shape/dtype sweeps in interpret
mode (per-kernel allclose, as required by the brief)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import local_train
from repro.kernels import (decode_apply_plan, decode_apply_ring,
                           encode_delta, make_fused_momentum_update,
                           momentum_update_flat)
from repro.kernels import ref
from repro.kernels.dequant_mix import (dequant_mix_momentum_buffer_pallas,
                                       dequant_mix_pallas)
from repro.kernels.momentum_sgd import momentum_sgd_pallas
from repro.kernels.quantize_pack import (
    momentum_quantize_pack_buffer_pallas, quantize_pack_pallas)

BITS = (2, 4, 8, 16)
SIZES = (1, 100, 512, 2048, 5000, 65536)


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("n", SIZES)
def test_quantize_pack_deterministic_matches_ref(bits, n):
    x = jax.random.normal(jax.random.PRNGKey(n + bits), (n,)) * 0.3
    words, s = encode_delta(x, bits, stochastic=False)
    expected = ref.quantize_pack_ref(x, bits, s)
    assert jnp.array_equal(words, expected)


@pytest.mark.parametrize("bits", (4, 8))
def test_quantize_pack_stochastic_matches_ref(bits):
    n = 3000
    x = jax.random.normal(jax.random.PRNGKey(0), (n,)) * 0.2
    per, w = ref.planar_pad_len(n, bits)
    noise = jax.random.uniform(jax.random.PRNGKey(1), (per, w))
    s = jnp.float32(0.01)
    x2d = jnp.pad(x, (0, per * w - n)).reshape(per, w)
    kernel = quantize_pack_pallas(x2d, s, noise, bits=bits, stochastic=True,
                                  interpret=True)
    expected = ref.quantize_pack_ref(jnp.pad(x, (0, per * w - n)), bits, s,
                                     noise=noise.reshape(-1))
    assert jnp.array_equal(kernel, expected)


@pytest.mark.parametrize("bits", (4, 8, 16))
@pytest.mark.parametrize("n", (64, 1000, 4096))
@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16))
def test_dequant_mix_matches_ref(bits, n, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(1), (n,))).astype(dtype)
    qs, ss = [], []
    for i in range(3):
        d = jax.random.normal(jax.random.PRNGKey(2 + i), (n,)) * 0.05
        wds, s = encode_delta(d, bits, stochastic=False)
        qs.append(wds)
        ss.append(s)
    scales = jnp.stack(ss)
    out = decode_apply_ring(x, qs[0], qs[1], qs[2], scales, bits=bits,
                            w_self=0.5, w_nb=0.25)
    expected = ref.dequant_mix_ref(x, qs[0], qs[1], qs[2], scales, bits,
                                   0.5, 0.25)
    atol = 1e-6 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32), atol=atol)


@pytest.mark.parametrize("bits", (4, 8, 16))
@pytest.mark.parametrize("k", (1, 3, 5))
@pytest.mark.parametrize("n", (100, 4096))
def test_dequant_mix_plan_matches_ref(bits, k, n):
    """Plan-generic fused apply (k wire streams, runtime weights) — the
    sparse GossipPlan backend's decode hot path."""
    x = jax.random.normal(jax.random.PRNGKey(1), (n,))
    words, scales = [], []
    for i in range(k):
        d = jax.random.normal(jax.random.PRNGKey(2 + i), (n,)) * 0.05
        w, s = encode_delta(d, bits, stochastic=False)
        words.append(w)
        scales.append(s)
    weights = jax.random.uniform(jax.random.PRNGKey(9), (k,))
    out = decode_apply_plan(x, jnp.stack(words), jnp.stack(scales), weights,
                            bits=bits)
    expected = x
    for i in range(k):
        expected = expected + weights[i] * ref.unpack_dequant_ref(
            words[i], bits, scales[i], n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=1e-5)


def _check_momentum_flat(n, theta, eta):
    ky, kv, kg = jax.random.split(jax.random.PRNGKey(n % 101), 3)
    y = jax.random.normal(ky, (n,))
    v = jax.random.normal(kv, (n,))
    g = jax.random.normal(kg, (n,))
    yo, vo = momentum_update_flat(y, v, g, eta, theta)
    yr, vr = ref.momentum_sgd_ref(y, v, g, eta, theta)
    np.testing.assert_allclose(np.asarray(yo), np.asarray(yr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(vo), np.asarray(vr), atol=1e-6)


@given(st.integers(1, 40000), st.sampled_from([0.0, 0.5, 0.9, 0.99]),
       st.sampled_from([1e-3, 1e-2, 0.1]))
@settings(max_examples=25, deadline=None)
def test_momentum_matches_ref(n, theta, eta):
    _check_momentum_flat(n, theta, eta)


def test_fused_update_in_local_train_bitexact():
    """Plugging the Pallas fused heavy-ball into local_train changes
    nothing numerically (the integration point used by launch.train)."""
    fused = make_fused_momentum_update(interpret=True)

    def loss_fn(p, b, r):
        return 0.5 * jnp.sum((p["w"] - b["c"]) ** 2) \
            + jnp.sum(jnp.tanh(p["u"]) * b["c"][:3].sum())

    p = {"w": jnp.ones((321,)), "u": jnp.full((3, 7), 0.1)}
    b = {"c": jnp.linspace(-1, 1, 321 * 4).reshape(4, 321)}
    y1, l1 = local_train(loss_fn, p, b, jax.random.PRNGKey(0),
                         eta=0.02, theta=0.9)
    y2, l2 = local_train(loss_fn, p, b, jax.random.PRNGKey(0),
                         eta=0.02, theta=0.9, fused_update=fused)
    for a, c in zip(jax.tree.leaves(y1), jax.tree.leaves(y2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-6)
    assert float(l1) == float(l2)


@pytest.mark.parametrize("bits", BITS)
def test_wire_volume_is_b_over_32(bits):
    """The packed message is b/32 of the float payload (+1 scale word)."""
    n = 4096
    x = jax.random.normal(jax.random.PRNGKey(0), (n,))
    words, s = encode_delta(x, bits, stochastic=False)
    payload_words = n * bits / 32
    assert words.size >= payload_words          # padding only adds
    assert words.size <= payload_words + ref.LANE_BLOCK
    assert words.dtype == jnp.uint32


def test_quantize_pack_error_bound():
    """Kernel roundtrip error <= s per coordinate (Assumption 4 basis)."""
    for bits in BITS:
        n = 2000
        x = jax.random.normal(jax.random.PRNGKey(bits), (n,))
        words, s = encode_delta(x, bits, stochastic=False)
        back = ref.unpack_dequant_ref(words, bits, s, n)
        assert float(jnp.abs(back - x).max()) <= float(s) * (1 + 1e-5)


# ---------------------------------------------------------------------------
# Fused-round kernels: runtime eta/theta, ragged shapes, encode/decode fusion
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", ((3, 700), (1, 1), (9, 513), (8, 512)))
def test_momentum_pallas_ragged_pad_and_slice(shape):
    """Shapes off the (ROW_BLOCK, LANE_BLOCK) grid are padded inside the
    wrapper and sliced back — e.g. R=3, C=700 must NOT read out of bounds
    or leak padding into the output."""
    r, c = shape
    ky, kv, kg = jax.random.split(jax.random.PRNGKey(r * 1000 + c), 3)
    y = jax.random.normal(ky, shape)
    v = jax.random.normal(kv, shape)
    g = jax.random.normal(kg, shape)
    yo, vo = momentum_sgd_pallas(y, v, g, eta=0.05, theta=0.9,
                                 interpret=True)
    yr, vr = ref.momentum_sgd_ref(y, v, g, 0.05, 0.9)
    assert yo.shape == shape and vo.shape == shape
    np.testing.assert_allclose(np.asarray(yo), np.asarray(yr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(vo), np.asarray(vr), atol=1e-6)


def test_momentum_pallas_traced_eta_batches_under_vmap():
    """eta/theta are RUNTIME operands: a vmap over per-client traced etas
    (the async staleness-adaptive path) runs ONE kernel, values matching
    the per-client XLA update."""
    m, shape = 4, (8, 512)
    etas = jnp.asarray([0.0, 0.01, 0.05, 0.1], jnp.float32)
    ky, kv, kg = jax.random.split(jax.random.PRNGKey(3), 3)
    y = jax.random.normal(ky, (m,) + shape)
    v = jax.random.normal(kv, (m,) + shape)
    g = jax.random.normal(kg, (m,) + shape)

    @jax.jit
    def run(y, v, g, etas):
        return jax.vmap(lambda yy, vv, gg, e: momentum_sgd_pallas(
            yy, vv, gg, eta=e, theta=0.9, interpret=True))(y, v, g, etas)

    yo, vo = run(y, v, g, etas)
    for i in range(m):
        yr, vr = ref.momentum_sgd_ref(y[i], v[i], g[i], etas[i], 0.9)
        np.testing.assert_allclose(np.asarray(yo[i]), np.asarray(yr),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(vo[i]), np.asarray(vr),
                                   atol=1e-6)


@pytest.mark.parametrize("bits", (4, 8))
@pytest.mark.parametrize("stochastic", (False, True))
def test_fused_encode_kernel_matches_ref(bits, stochastic):
    """momentum_quantize_pack fusion: the applied last local step AND the
    packed wire in one pass — integer wire BITWISE vs the oracle, float
    outputs to ~ulp (FMA contraction)."""
    per, w = 32 // bits, 2 * ref.LANE_BLOCK
    nb = w // ref.LANE_BLOCK
    keys = jax.random.split(jax.random.PRNGKey(bits), 6)
    y, v, g, x = (jax.random.normal(k, (per, w)) * 0.3 for k in keys[:4])
    sblk = jax.random.uniform(keys[4], (1, nb), minval=0.01, maxval=0.1)
    noise = jax.random.uniform(keys[5], (per, w))
    et = jnp.asarray([0.05, 0.9], jnp.float32)
    yo, vo, words = momentum_quantize_pack_buffer_pallas(
        y, v, g, x, sblk, noise, et, bits=bits, stochastic=stochastic,
        interpret=True)
    yr, vr, wr = ref.momentum_quantize_pack_buffer_ref(
        y, v, g, x, sblk[0], bits, 0.05, 0.9,
        noise=noise if stochastic else None)
    assert jnp.array_equal(words, wr), "fused encode wire is not bitwise"
    np.testing.assert_allclose(np.asarray(yo), np.asarray(yr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(vo), np.asarray(vr), atol=1e-6)


@pytest.mark.parametrize("bits", (4, 8))
@pytest.mark.parametrize("k", (1, 3))
def test_fused_decode_kernel_matches_ref(bits, k):
    """dequant_mix_momentum fusion: mix + the deferred last heavy-ball
    step in one pass, vs the tree-level oracle."""
    per, w = 32 // bits, 2 * ref.LANE_BLOCK
    nb = w // ref.LANE_BLOCK
    rng = np.random.default_rng(bits * 10 + k)
    x = jnp.asarray(rng.normal(size=(per, w)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(per, w)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(per, w)), jnp.float32)
    streams = jnp.asarray(
        rng.integers(0, 2 ** 32, size=(k, w), dtype=np.uint32))
    sblk = jnp.asarray(rng.uniform(0.01, 0.1, size=(k, nb)), jnp.float32)
    weights = jnp.asarray(rng.uniform(0.0, 0.5, size=(k,)), jnp.float32)
    et = jnp.asarray([0.05, 0.9], jnp.float32)
    out = dequant_mix_momentum_buffer_pallas(
        x, streams, sblk, weights, v, g, et, bits=bits, interpret=True)
    expected = ref.dequant_mix_momentum_buffer_ref(
        x, streams, sblk, weights, v, g, et, bits)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=1e-5)
