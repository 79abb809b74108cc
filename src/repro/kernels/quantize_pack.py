"""Pallas TPU kernel: b-bit quantize + planar bit-pack (wire encoder).

This is the per-round communication hot spot of quantized DFedAvgM: every
client encodes its model delta before the neighbor exchange. The encode is
purely elementwise + a tiny sublane reduction, so the kernel streams the
delta through VMEM once and writes 32/b-fold fewer bytes back to HBM.

Layout (see kernels.ref): input is viewed as [per, W] with the lane axis W
a multiple of 128; word w ORs together the offset-encoded fields of
column w across the ``per`` sublanes — all shifts are lane-parallel.

Grid: 1-D over tiles of G lane blocks (``kernels.tiling``); each lane
block of LANE_BLOCK words is quantized with its own scale. VMEM per
step: (per f32 in + per f32 noise + one u32 out) x G * LANE_BLOCK,
double-buffered — b=8, G=64: 36 B * 32K * 2 ≈ 2.3 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import LANE_BLOCK
from .tiling import for_each_block, lane_tiles, scale_groups


def _pack_rows(fields: jnp.ndarray, bits: int) -> jnp.ndarray:
    """[per, LANE_BLOCK] b-bit fields -> [1, LANE_BLOCK] words: row r lands at
    bit ``r * bits``. The fields are disjoint, so OR-ing row by row gives
    the oracle's shifted sum without a reduction over unsigned integers,
    which the TPU compiler does not lower."""
    words = fields[0:1]
    for r in range(1, 32 // bits):
        words = words | (fields[r:r + 1] << (r * bits))
    return words


def _encode_block(x, noise_ref, lanes, s, *, bits: int, stochastic: bool):
    """One lane block's words: quantize ``x`` [per, LANE_BLOCK] f32 with
    step ``s`` (stochastic rounding against ``noise_ref[:, lanes]``),
    offset-encode and pack — the oracle's expression, in its order."""
    qmin = -(2 ** (bits - 1))
    qmax = 2 ** (bits - 1) - 1
    a = x / s
    k = jnp.floor(a)
    if stochastic:
        k = k + (noise_ref[:, lanes] < (a - k)).astype(jnp.float32)
    k = jnp.clip(k, qmin, qmax).astype(jnp.int32)
    fields = (k + (1 << (bits - 1))).astype(jnp.uint32)
    return _pack_rows(fields, bits)                        # [1, LANE_BLOCK]


def _quantize_pack_kernel(x_ref, noise_ref, s_ref, out_ref, *, bits: int,
                          stochastic: bool, g: int):
    def block(lanes, scale):
        out_ref[:, lanes] = _encode_block(
            x_ref[:, lanes], noise_ref, lanes, scale(0), bits=bits,
            stochastic=stochastic)

    for_each_block(g, s_ref, block)


@functools.partial(jax.jit,
                   static_argnames=("bits", "stochastic", "interpret"))
def quantize_pack_buffer_pallas(x2d: jnp.ndarray, s_blocks: jnp.ndarray,
                                noise: jnp.ndarray, *, bits: int,
                                stochastic: bool, interpret: bool = False
                                ) -> jnp.ndarray:
    """Flat-wire-buffer encoder: one ``pallas_call`` quantizes and packs a
    whole model's planar buffer with PER-LANE-BLOCK scales.

    x2d: [per, W] f32 (a ``core.wire_layout.WireLayout`` buffer, leaf
    segments block-aligned); s_blocks: f32 [1, W // LANE_BLOCK] — block
    ``i`` reads its owning leaf's scale, so per-leaf quantization survives
    the flattening; noise: [per, W] (ignored unless stochastic). Returns
    uint32 [W].
    """
    per, w = x2d.shape
    assert per == 32 // bits and w % LANE_BLOCK == 0, (per, w)
    n_blocks = w // LANE_BLOCK
    assert s_blocks.shape == (1, n_blocks), (s_blocks.shape, n_blocks)
    out = jax.ShapeDtypeStruct((1, w), jnp.uint32)
    tiles = lane_tiles(n_blocks, x2d, noise, out)
    kernel = functools.partial(_quantize_pack_kernel, bits=bits,
                               stochastic=stochastic, g=tiles.g)
    return pl.pallas_call(
        kernel,
        grid=tiles.grid,
        in_specs=[tiles.spec(per), tiles.spec(per), tiles.scale_spec(1)],
        # Words leave as a [1, W] row: the TPU tiles a 1-D uint32 array
        # by 1024, which a 1-D tile does not always match.
        out_specs=tiles.spec(1),
        out_shape=out,
        interpret=interpret,
        name="quantize_pack_buffer",
    )(x2d, noise, scale_groups(s_blocks))[0]


def quantize_pack_pallas(x2d: jnp.ndarray, s: jnp.ndarray,
                         noise: jnp.ndarray, *, bits: int,
                         stochastic: bool, interpret: bool = False
                         ) -> jnp.ndarray:
    """x2d: [per, W] f32 (pre-padded, W % LANE_BLOCK == 0); s: scalar f32;
    noise: [per, W] f32 (ignored unless stochastic). Returns uint32 [W]:
    :func:`quantize_pack_buffer_pallas` with ``s`` on every lane block."""
    n_blocks = x2d.shape[1] // LANE_BLOCK
    s_blocks = jnp.broadcast_to(jnp.asarray(s, jnp.float32).reshape(1, 1),
                                (1, n_blocks))
    return quantize_pack_buffer_pallas(x2d, s_blocks, noise, bits=bits,
                                       stochastic=stochastic,
                                       interpret=interpret)
