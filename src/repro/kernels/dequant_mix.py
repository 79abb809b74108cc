"""Pallas TPU kernel: fused unpack + dequantize + ring gossip apply.

Computes, for one client's flat parameter block (paper eq. 7 with ring
weights):

    out = x + w_self * deq(q_own) + w_nb * deq(q_left) + w_nb * deq(q_right)

in ONE pass: the three packed uint32 streams are unpacked in VMEM and the
weighted sum is applied directly to x, instead of materializing three
dequantized f32 tensors in HBM (saves 3 full-size HBM writes + reads per
round; the op is strictly bandwidth-bound).

Layout matches quantize_pack: planar [per, W] view, lane axis blocked by
LANE_BLOCK.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .quantize_pack import block_scale, scale_groups, scale_spec
from .ref import LANE_BLOCK


def _dequant_mix_buffer_kernel(x_ref, q_ref, s_ref, w_ref, out_ref, *,
                               bits: int, n_streams: int):
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
    per = 32 // bits
    mask = jnp.uint32((1 << bits) - 1)
    offset = jnp.int32(1 << (bits - 1))
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (per, 1), 0) * bits

    acc = x_ref[...].astype(jnp.float32)
    for k in range(n_streams):
        fields = (q_ref[k][None, :] >> shifts) & mask
        deq = (fields.astype(jnp.int32) - offset).astype(jnp.float32) \
            * block_scale(s_ref, k)
        acc += w_ref[0, k] * deq
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def dequant_mix_buffer_pallas(x2d: jnp.ndarray, streams: jnp.ndarray,
                              block_scales: jnp.ndarray,
                              weights: jnp.ndarray, *, bits: int,
                              interpret: bool = False) -> jnp.ndarray:
    """x2d: [per, W] (f32/bf16) planar buffer; streams: uint32 [k, W];
    block_scales: f32 [k, W // LANE_BLOCK]; weights: f32 [k] (traced OK).
    Returns [per, W]. VMEM per step: (per + k) * LANE_BLOCK words — e.g.
    b=8, k=5: 9 * 512 * 4 B ≈ 18 KiB, far under budget."""
    per, w = x2d.shape
    k = streams.shape[0]
    n_blocks = w // LANE_BLOCK
    assert per == 32 // bits and w % LANE_BLOCK == 0, (per, w)
    assert block_scales.shape == (k, n_blocks), (block_scales.shape, k)
    kernel = functools.partial(_dequant_mix_buffer_kernel, bits=bits,
                               n_streams=k)
    return pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((per, LANE_BLOCK), lambda i: (0, i)),
            pl.BlockSpec((k, LANE_BLOCK), lambda i: (0, i)),
            scale_spec(k),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((per, LANE_BLOCK), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=interpret,
        name="dequant_mix_buffer",
    )(x2d, streams, scale_groups(block_scales),
      weights.reshape(1, k).astype(jnp.float32))


def _dequant_mix_momentum_buffer_kernel(x_ref, q_ref, s_ref, w_ref, v_ref,
                                        g_ref, et_ref, out_ref, *, bits: int,
                                        n_streams: int):
    """Fused mix + deferred momentum: the round's combined decode-apply AND
    final heavy-ball update in one memory pass —

        out = [x + sum_k w[k] * deq(stream[k], scale[k, block])]
              + (theta * v - eta * g)

    The (v, g) pair is the round's DEFERRED last local step (fused-round
    mode holds it back past the wire): mix -> v' = theta*v - eta*g ->
    y' = mixed + v' without a second trip over the model. No v output —
    momentum restarts at 0 every round (Algorithm 1), so v' dies here.
    eta/theta are runtime scalars in et_ref = [[eta, theta]].
    """
    per = 32 // bits
    mask = jnp.uint32((1 << bits) - 1)
    offset = jnp.int32(1 << (bits - 1))
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (per, 1), 0) * bits

    acc = x_ref[...].astype(jnp.float32)
    for k in range(n_streams):
        fields = (q_ref[k][None, :] >> shifts) & mask
        deq = (fields.astype(jnp.int32) - offset).astype(jnp.float32) \
            * block_scale(s_ref, k)
        acc += w_ref[0, k] * deq
    v_next = (et_ref[0, 1] * v_ref[...].astype(jnp.float32)
              - et_ref[0, 0] * g_ref[...].astype(jnp.float32))
    out_ref[...] = (acc + v_next).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def dequant_mix_momentum_buffer_pallas(x2d: jnp.ndarray, streams: jnp.ndarray,
                                       block_scales: jnp.ndarray,
                                       weights: jnp.ndarray, v2d: jnp.ndarray,
                                       g2d: jnp.ndarray, et: jnp.ndarray, *,
                                       bits: int, interpret: bool = False
                                       ) -> jnp.ndarray:
    """Fused-round decoder: x2d: [per, W] planar base; streams: uint32
    [k, W]; block_scales: f32 [k, W // LANE_BLOCK]; weights: f32 [k];
    v2d/g2d: [per, W] planar velocity/gradient of the deferred step; et:
    f32 [2] = (eta, theta) — all runtime (traced OK). Returns [per, W]:
    the mixed params with the deferred momentum step applied. Oracle:
    ``kernels.ref.dequant_mix_momentum_buffer_ref``."""
    per, w = x2d.shape
    k = streams.shape[0]
    n_blocks = w // LANE_BLOCK
    assert per == 32 // bits and w % LANE_BLOCK == 0, (per, w)
    assert block_scales.shape == (k, n_blocks), (block_scales.shape, k)
    kernel = functools.partial(_dequant_mix_momentum_buffer_kernel,
                               bits=bits, n_streams=k)
    buf = pl.BlockSpec((per, LANE_BLOCK), lambda i: (0, i))
    return pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            buf,
            pl.BlockSpec((k, LANE_BLOCK), lambda i: (0, i)),
            scale_spec(k),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            buf, buf,
            pl.BlockSpec((1, 2), lambda i: (0, 0)),
        ],
        out_specs=buf,
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=interpret,
        name="dequant_mix_momentum_buffer",
    )(x2d, streams, scale_groups(block_scales),
      weights.reshape(1, k).astype(jnp.float32), v2d, g2d,
      et.reshape(1, 2).astype(jnp.float32))


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
