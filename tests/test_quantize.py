"""Quantizer properties (paper §3.2, Assumption 4) + wire format."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.quantize import (QuantConfig, dequantize_int, message_bits,
                                 pack_bits, quantize, quantize_int,
                                 quantize_pytree, dequantize_pytree,
                                 unpack_bits)


@given(st.integers(0, 2**31 - 1), st.sampled_from([2, 4, 8, 16]),
       st.integers(1, 400))
@settings(max_examples=60, deadline=None)
def test_pack_roundtrip_exact(seed, bits, n):
    cfg = QuantConfig(bits=bits, stochastic=False)
    x = jax.random.normal(jax.random.PRNGKey(seed % 1000), (n,))
    k, s = quantize_int(x, cfg)
    assert int(k.min()) >= cfg.qmin and int(k.max()) <= cfg.qmax
    words = pack_bits(k, bits)
    k2 = unpack_bits(words, bits, n)
    assert jnp.array_equal(k, k2)


@given(st.integers(0, 10_000), st.sampled_from([4, 8, 16]))
@settings(max_examples=40, deadline=None)
def test_assumption4_error_bound(seed, bits):
    """E||Q(x)-x||^2 <= d * s^2 pointwise (deterministic floor: err < s;
    the paper's d/4 s^2 bound holds in expectation for centered schemes —
    we check the per-coordinate guarantee |q(a)-a| <= s)."""
    cfg = QuantConfig(bits=bits, stochastic=False)
    x = jax.random.normal(jax.random.PRNGKey(seed % 997), (512,))
    k, s = quantize_int(x, cfg)
    err = jnp.abs(dequantize_int(k, s) - x)
    assert float(err.max()) <= float(s) * (1 + 1e-5)


def test_stochastic_unbiased():
    """E[q(a)] = a for stochastic rounding (paper: 'easy to see')."""
    cfg = QuantConfig(bits=8, stochastic=True, scale_mode="fixed", s=0.1)
    a = jnp.full((20000,), 0.537)
    k, s = quantize_int(a, cfg, key=jax.random.PRNGKey(0))
    mean = float(dequantize_int(k, s).mean())
    assert abs(mean - 0.537) < 2e-3


def test_fixed_vs_pertensor_scale():
    x = jnp.linspace(-1, 1, 256)
    qf = quantize(x, QuantConfig(bits=8, stochastic=False,
                                 scale_mode="fixed", s=0.05))
    assert float(jnp.abs(qf - x).max()) <= 0.05 + 1e-6
    qp = quantize(x, QuantConfig(bits=8, stochastic=False))
    # per-tensor scale adapts: error <= max|x|/qmax
    assert float(jnp.abs(qp - x).max()) <= 1.0 / 127 + 1e-6


def test_bits32_passthrough():
    cfg = QuantConfig(bits=32)
    x = jnp.array([1.5, -2.25, 0.0])
    assert jnp.array_equal(quantize(x, cfg), x)
    k = jnp.array([1, -5, 300], jnp.int32)
    assert jnp.array_equal(unpack_bits(pack_bits(k, 32), 32, 3), k)


def test_pytree_roundtrip():
    tree = {"a": jnp.ones((7, 3)), "b": {"c": jnp.linspace(-1, 1, 50)}}
    cfg = QuantConfig(bits=8, stochastic=False)
    wire, scales = quantize_pytree(tree, cfg)
    back = dequantize_pytree(wire, scales, tree, cfg)
    for l1, l2 in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert l1.shape == l2.shape
        assert float(jnp.abs(l1 - l2).max()) < 0.02
    # wire is uint32
    assert all(w.dtype == jnp.uint32 for w in jax.tree.leaves(wire))


def test_message_bits_formula():
    """Paper: quantized message = 32 + d*b bits; unquantized = 32d."""
    assert message_bits(1000, QuantConfig(bits=8)) == 32 + 8000
    assert message_bits(1000, QuantConfig(bits=32)) == 32000


@given(st.sampled_from([2, 4, 8, 16]))
@settings(deadline=None)
def test_quantized_grid_range(bits):
    """Representable range is {-2^{b-1}s, ..., (2^{b-1}-1)s}."""
    cfg = QuantConfig(bits=bits, stochastic=False, scale_mode="fixed", s=1.0)
    x = jnp.array([-1e9, 1e9])
    k, s = quantize_int(x, cfg)
    assert int(k[0]) == -(2 ** (bits - 1))
    assert int(k[1]) == 2 ** (bits - 1) - 1
