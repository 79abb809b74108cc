"""Pallas TPU kernel: fused unpack + dequantize + ring gossip apply.

Computes, for one client's flat parameter block (paper eq. 7 with ring
weights):

    out = x + w_self * deq(q_own) + w_nb * deq(q_left) + w_nb * deq(q_right)

in ONE pass: the three packed uint32 streams are unpacked in VMEM and the
weighted sum is applied directly to x, instead of materializing three
dequantized f32 tensors in HBM (saves 3 full-size HBM writes + reads per
round; the op is strictly bandwidth-bound).

Layout matches quantize_pack: planar [per, W] view, lane axis tiled by
G lane blocks a grid step (``kernels.tiling``), each LANE_BLOCK-word
block dequantized with its own scale. VMEM per step: (per base in + k
u32 streams + per out) x G * LANE_BLOCK words, double-buffered — b=4,
k=5, f32, G=64: 84 B * 32K * 2 ≈ 5.3 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import LANE_BLOCK
from .tiling import for_each_block, lane_tiles, scale_groups


def _mix_block(x_ref, q_ref, w_ref, lanes, scale, *, bits: int,
               n_streams: int):
    """One lane block of ``x + sum_k w[k] * deq(stream[k], scale(k))``,
    f32, streams in order."""
    mask = jnp.uint32((1 << bits) - 1)
    offset = jnp.int32(1 << (bits - 1))
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (32 // bits, 1), 0) * bits
    acc = x_ref[:, lanes].astype(jnp.float32)
    for k in range(n_streams):
        fields = (q_ref[pl.ds(k, 1), lanes] >> shifts) & mask
        deq = (fields.astype(jnp.int32) - offset).astype(jnp.float32) \
            * scale(k)
        acc += w_ref[0, k] * deq
    return acc


def _dequant_mix_buffer_kernel(x_ref, q_ref, s_ref, w_ref, out_ref, *,
                               bits: int, n_streams: int, g: int):
    """Flat-wire-buffer fused apply: the whole model's planar buffer in
    one kernel, with PER-LANE-BLOCK scales (each block carries its owning
    leaf's scale — see ``core.wire_layout``):

        out = x + sum_k w[k] * deq(stream[k], scale[k, block])

    Streams are the client's OWN packed words plus one received stream per
    plan step; scales and weights are runtime values (per-round gathered
    weights of a time-varying ``W_t``). Replaces one dequantized f32
    tensor per stream in HBM with a single VMEM pass over the buffer.
    Same accumulation order as ``ref.dequant_mix_buffer_ref``; equality
    with the oracle is a few ulp, not bitwise (FMA contraction is a
    per-compilation choice — see the oracle's docstring).
    """
    def block(lanes, scale):
        acc = _mix_block(x_ref, q_ref, w_ref, lanes, scale, bits=bits,
                         n_streams=n_streams)
        out_ref[:, lanes] = acc.astype(out_ref.dtype)

    for_each_block(g, s_ref, block)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def dequant_mix_buffer_pallas(x2d: jnp.ndarray, streams: jnp.ndarray,
                              block_scales: jnp.ndarray,
                              weights: jnp.ndarray, *, bits: int,
                              interpret: bool = False) -> jnp.ndarray:
    """x2d: [per, W] (f32/bf16) planar buffer; streams: uint32 [k, W];
    block_scales: f32 [k, W // LANE_BLOCK]; weights: f32 [k] (traced OK).
    Returns [per, W]."""
    per, w = x2d.shape
    k = streams.shape[0]
    n_blocks = w // LANE_BLOCK
    assert per == 32 // bits and w % LANE_BLOCK == 0, (per, w)
    assert block_scales.shape == (k, n_blocks), (block_scales.shape, k)
    out = jax.ShapeDtypeStruct(x2d.shape, x2d.dtype)
    tiles = lane_tiles(n_blocks, x2d, streams, out)
    kernel = functools.partial(_dequant_mix_buffer_kernel, bits=bits,
                               n_streams=k, g=tiles.g)
    return pl.pallas_call(
        kernel,
        grid=tiles.grid,
        in_specs=[
            tiles.spec(per),
            tiles.spec(k),
            tiles.scale_spec(k),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_specs=tiles.spec(per),
        out_shape=out,
        interpret=interpret,
        name="dequant_mix_buffer",
    )(x2d, streams, scale_groups(block_scales),
      weights.reshape(1, k).astype(jnp.float32))


def dequant_mix_plan_pallas(x2d: jnp.ndarray, streams: jnp.ndarray,
                            scales: jnp.ndarray, weights: jnp.ndarray, *,
                            bits: int, interpret: bool = False
                            ) -> jnp.ndarray:
    """x2d: [per, W] (f32/bf16); streams: uint32 [k, W]; scales/weights:
    f32 [k] (traced OK — the per-round mask). Returns [per, W]:
    :func:`dequant_mix_buffer_pallas` with stream k's one scale on every
    lane block."""
    k, w = streams.shape
    block_scales = jnp.broadcast_to(
        jnp.asarray(scales, jnp.float32).reshape(k, 1),
        (k, w // LANE_BLOCK))
    return dequant_mix_buffer_pallas(x2d, streams, block_scales, weights,
                                     bits=bits, interpret=interpret)


def dequant_mix_pallas(x2d: jnp.ndarray, q_own: jnp.ndarray,
                       q_left: jnp.ndarray, q_right: jnp.ndarray,
                       scales: jnp.ndarray, *, bits: int, w_self: float,
                       w_nb: float, interpret: bool = False) -> jnp.ndarray:
    """x2d: [per, W] (f32/bf16); q_*: uint32 [W]; scales: f32 [3] (own,
    left, right). Returns [per, W]: :func:`dequant_mix_plan_pallas` over
    the three ring streams."""
    return dequant_mix_plan_pallas(
        x2d, jnp.stack([q_own, q_left, q_right]), scales,
        jnp.asarray([w_self, w_nb, w_nb], jnp.float32), bits=bits,
        interpret=interpret)
