"""Pallas kernels vs pure-jnp ref oracles: shape/dtype sweeps in interpret
mode (per-kernel allclose, as required by the brief)."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import local_train
from repro.kernels import (decode_apply_plan, decode_apply_ring,
                           encode_delta, make_fused_momentum_update,
                           momentum_update_flat)
from repro.kernels import ref, tiling
from repro.kernels.dequant_mix import (dequant_mix_buffer_pallas,
                                       dequant_mix_pallas)
from repro.kernels.momentum_sgd import momentum_sgd_pallas
from repro.kernels.quantize_pack import (quantize_pack_buffer_pallas,
                                         quantize_pack_pallas)

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
# Momentum kernel: runtime eta/theta, ragged shapes
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


# ---------------------------------------------------------------------------
# Buffer kernels over lane tiles: G lane blocks a grid step, each with its
# own scale; RAGGED_BLOCKS leaves the last step of every tile size ragged.
# ---------------------------------------------------------------------------

RAGGED_BLOCKS = 130          # 2 x 65: no multiple of any G of 4 or more
N_BLOCKS = (2, RAGGED_BLOCKS)


def _blockwise(bits, n_blocks, key, n=1):
    """``n`` [per, W] f32 buffers whose lane blocks each have a magnitude
    of their own, and [1, n_blocks] scales that fit the first one block
    by block (absmax / qmax, as ``core.wire_layout`` sets them), so a
    lane block quantized or dequantized with a neighbour's scale differs.
    """
    per, w = 32 // bits, n_blocks * ref.LANE_BLOCK
    keys = jax.random.split(key, n + 1)
    mag = jax.random.uniform(keys[0], (n_blocks,), minval=0.05, maxval=2.0)
    mag = jnp.repeat(mag, ref.LANE_BLOCK)[None, :]
    bufs = [jax.random.normal(k, (per, w)) * mag for k in keys[1:]]
    absmax = jnp.abs(bufs[0]).reshape(per, n_blocks, -1).max(axis=(0, 2))
    return bufs, (absmax / (2 ** (bits - 1) - 1))[None, :]


def _grid_and_vmem(fn, *args) -> tuple[int, int, int]:
    """(grid steps, lanes a step, bytes of a step's VMEM blocks
    double-buffered) of the one ``pallas_call`` in ``fn`` traced at
    ``args``."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for v in eqn.params.values():
                sub = getattr(v, "jaxpr", None)
                if sub is not None:
                    yield from walk(getattr(sub, "jaxpr", sub))
    (eqn,) = walk(jax.make_jaxpr(fn)(*args).jaxpr)
    gm = eqn.params["grid_mapping"]
    vmem = [bm.block_aval for bm in gm.block_mappings
            if bm.block_aval.memory_space is None]
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in vmem)
    (steps,) = gm.grid
    return steps, vmem[0].shape[-1], 2 * nbytes


def _assert_ragged(fn, *args, n_blocks):
    steps, lanes, _ = _grid_and_vmem(fn, *args)
    g = lanes // ref.LANE_BLOCK
    assert steps == -(-n_blocks // g)
    if n_blocks == RAGGED_BLOCKS:
        assert g > 1 and n_blocks % g, (g, n_blocks)


@pytest.mark.parametrize("bits", (4, 8))
@pytest.mark.parametrize("stochastic", (False, True))
@pytest.mark.parametrize("n_blocks", N_BLOCKS)
def test_quantize_pack_buffer_tiles_match_ref(bits, stochastic, n_blocks):
    """Encode over lane tiles: words bitwise the oracle's, with a scale of
    its own on every lane block and a ragged last grid step."""
    (x,), sblk = _blockwise(bits, n_blocks, jax.random.PRNGKey(bits))
    noise = jax.random.uniform(jax.random.PRNGKey(7), x.shape)
    fn = functools.partial(quantize_pack_buffer_pallas, bits=bits,
                           stochastic=stochastic, interpret=True)
    _assert_ragged(fn, x, sblk, noise, n_blocks=n_blocks)
    words = fn(x, sblk, noise)
    expected = ref.quantize_pack_buffer_ref(
        x, sblk[0], bits, noise=noise if stochastic else None)
    assert words.shape == (n_blocks * ref.LANE_BLOCK,)
    assert jnp.array_equal(words, expected)


@pytest.mark.parametrize("bits", (4, 8))
@pytest.mark.parametrize("k", (1, 2, 3, 5))
@pytest.mark.parametrize("n_blocks", N_BLOCKS)
def test_dequant_mix_buffer_tiles_match_ref(bits, k, n_blocks):
    """Decode-apply over lane tiles: k streams (k=1: a client with no
    neighbour stream that round), a scale of their own on every lane
    block of every stream, a ragged last grid step."""
    per, w = 32 // bits, n_blocks * ref.LANE_BLOCK
    rng = np.random.default_rng(bits * 10 + k)
    x = jnp.asarray(rng.normal(size=(per, w)), jnp.float32)
    streams = jnp.asarray(
        rng.integers(0, 2 ** 32, size=(k, w), dtype=np.uint32))
    sblk = jnp.asarray(rng.uniform(0.01, 0.1, size=(k, n_blocks)),
                       jnp.float32)
    weights = jnp.asarray(rng.uniform(0.0, 0.5, size=(k,)), jnp.float32)
    fn = functools.partial(dequant_mix_buffer_pallas, bits=bits,
                           interpret=True)
    _assert_ragged(fn, x, streams, sblk, weights, n_blocks=n_blocks)
    out = fn(x, streams, sblk, weights)
    o = np.asarray(ref.dequant_mix_buffer_ref(x, streams, sblk, weights,
                                              bits))
    tol = 8 * np.finfo(np.float32).eps * (np.abs(o).max() + 1.0)
    np.testing.assert_allclose(np.asarray(out), o, rtol=0, atol=tol)


# The benchmark cells' wire layouts at q8: (model, layers) and the
# (per, W, lane blocks) they give.
CELL_WIRES = {"olmo-1b": (2, (4, 59_310_080, 115_840)),
              "mamba2-780m": (4, (4, 33_950_208, 66_309))}


def _cell_layout(model: str, bits: int):
    """The wire layout of a benchmark cell's model at published widths
    and the cell's depth, in the config's dtypes (Mamba2 mixes bf16
    weights with f32 ``A_log``/``dt_bias``)."""
    from repro.configs import get_config
    from repro.core.wire_layout import WireLayout
    from repro.models import model as M
    cfg = dataclasses.replace(get_config(model),
                              n_layers=CELL_WIRES[model][0])
    params = jax.eval_shape(lambda k: M.init_model(k, cfg)[0],
                            jax.random.PRNGKey(0))
    return WireLayout.for_tree(params, bits=bits)


_CODEC_CASES = [("olmo-1b", "encode", 1), ("olmo-1b", "decode", 2),
                ("olmo-1b", "decode", 3), ("mamba2-780m", "encode", 1),
                ("mamba2-780m", "decode", 2), ("mamba2-780m", "decode", 3)]


@pytest.mark.parametrize(
    "model,kernel,k", _CODEC_CASES,
    ids=[(f"{k}-{n}" if m == "olmo-1b" else f"mamba2-{k}-{n}")
         for m, k, n in _CODEC_CASES])
def test_codec_tiles_at_cell_width(model, kernel, k):
    """The benchmark cells' q8 wire (two-layer OLMo-1B: per 4, W
    59,310,080; four-layer Mamba2-780m: per 4, W 33,950,208): each codec
    ``pallas_call`` streams it in at most 2,048 grid steps, not one per
    lane block (115,840 and 66,309), and a step's double-buffered VMEM
    blocks stay under the budget. Traced only."""
    lay = _cell_layout(model, 8)
    per, w, nb = lay.per, lay.total_words, lay.n_blocks
    assert (per, w, nb) == CELL_WIRES[model][1]
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)   # noqa: E731
    buf, q = f32(per, w), jax.ShapeDtypeStruct((k, w), jnp.uint32)
    fn, args = {
        "encode": (functools.partial(quantize_pack_buffer_pallas, bits=8,
                                     stochastic=True),
                   (buf, f32(1, nb), buf)),
        "decode": (functools.partial(dequant_mix_buffer_pallas, bits=8),
                   (buf, q, f32(k, nb), f32(k))),
    }[kernel]
    steps, lanes, vmem = _grid_and_vmem(fn, *args)
    assert steps <= 2048, (steps, lanes)
    assert steps == -(-nb // (lanes // ref.LANE_BLOCK))
    assert vmem <= tiling.VMEM_BUDGET, vmem
