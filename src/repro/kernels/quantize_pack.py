"""Pallas TPU kernel: b-bit quantize + planar bit-pack (wire encoder).

This is the per-round communication hot spot of quantized DFedAvgM: every
client encodes its model delta before the neighbor exchange. The encode is
purely elementwise + a tiny sublane reduction, so the kernel streams the
delta through VMEM once and writes 32/b-fold fewer bytes back to HBM.

Layout (see kernels.ref): input is viewed as [per, W] with the lane axis W
a multiple of 128; word w ORs together the offset-encoded fields of
column w across the ``per`` sublanes — all shifts are lane-parallel.

Grid: 1-D over lane blocks of LANE_BLOCK words.
VMEM per step: per*LANE_BLOCK f32 in + (optional) noise + LANE_BLOCK u32
out — e.g. b=8: 4*512*4 B + 512*4 B ≈ 10 KiB, far under the ~16 MiB VMEM
budget; LANE_BLOCK could be raised 256x before VMEM pressure, but the
kernel is bandwidth-bound either way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import LANE_BLOCK

# Per-lane-block scales reach the kernels through SMEM, SCALE_GROUP blocks
# at a time: a (rows, 1) block of a [rows, n_blocks] array is no legal TPU
# block (its last dim is neither a multiple of 128 nor the whole array's),
# so grid step i reads column i % SCALE_GROUP of scale block
# i // SCALE_GROUP.
SCALE_GROUP = 128


def scale_groups(block_scales: jnp.ndarray) -> jnp.ndarray:
    """f32 [rows, n_blocks] -> [rows, n_blocks rounded up to SCALE_GROUP]
    (zero-padded; the padded columns belong to no grid step)."""
    pad = -block_scales.shape[-1] % SCALE_GROUP
    return jnp.pad(block_scales.astype(jnp.float32), ((0, 0), (0, pad)))


def scale_spec(rows: int) -> pl.BlockSpec:
    """SMEM block of ``rows`` x SCALE_GROUP per-block scales (pair it with
    :func:`scale_groups` and :func:`block_scale`)."""
    return pl.BlockSpec((rows, SCALE_GROUP), lambda i: (0, i // SCALE_GROUP),
                        memory_space=pltpu.SMEM)


# Packed words leave the kernels as a [1, W] row: the TPU tiles a 1-D
# uint32 array by 1024, which a 1-D LANE_BLOCK block does not match.
_WORD_SPEC = pl.BlockSpec((1, LANE_BLOCK), lambda i: (0, i))


def block_scale(s_ref, row: int):
    """This grid step's scale in row ``row`` of a :func:`scale_spec` block."""
    return s_ref[row, pl.program_id(0) % SCALE_GROUP]


def _pack_rows(fields: jnp.ndarray, bits: int) -> jnp.ndarray:
    """[per, LANE_BLOCK] b-bit fields -> [1, LANE_BLOCK] words: row r lands at
    bit ``r * bits``. The fields are disjoint, so OR-ing row by row gives
    the oracle's shifted sum without a reduction over unsigned integers,
    which the TPU compiler does not lower."""
    words = fields[0:1]
    for r in range(1, 32 // bits):
        words = words | (fields[r:r + 1] << (r * bits))
    return words


def _quantize_pack_kernel(x_ref, noise_ref, s_ref, out_ref, *, bits: int,
                          stochastic: bool):
    per = 32 // bits
    qmin = -(2 ** (bits - 1))
    qmax = 2 ** (bits - 1) - 1
    s = block_scale(s_ref, 0)
    a = x_ref[...] / s                       # [per, LANE_BLOCK] f32
    k = jnp.floor(a)
    if stochastic:
        k = k + (noise_ref[...] < (a - k)).astype(jnp.float32)
    k = jnp.clip(k, qmin, qmax).astype(jnp.int32)
    fields = (k + (1 << (bits - 1))).astype(jnp.uint32)
    out_ref[...] = _pack_rows(fields, bits)                # [1, LANE_BLOCK]


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
    kernel = functools.partial(_quantize_pack_kernel, bits=bits,
                               stochastic=stochastic)
    return pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((per, LANE_BLOCK), lambda i: (0, i)),
            pl.BlockSpec((per, LANE_BLOCK), lambda i: (0, i)),
            scale_spec(1),
        ],
        out_specs=_WORD_SPEC,
        out_shape=jax.ShapeDtypeStruct((1, w), jnp.uint32),
        interpret=interpret,
        name="quantize_pack_buffer",
    )(x2d, noise, scale_groups(s_blocks))[0]


def _momentum_quantize_pack_kernel(y_ref, v_ref, g_ref, x_ref, noise_ref,
                                   s_ref, et_ref, y_out, v_out, w_out, *,
                                   bits: int, stochastic: bool):
    """Fused final-local-step + encode: apply the round's last heavy-ball
    update and emit the wire words as a SIDE OUTPUT of the same pass —

        v' = theta * v - eta * g ;  y' = y + v' ;  delta = y' - x ;
        words = pack(Q(delta / s))

    instead of a momentum pass (3R+2W of N) followed by a separate
    quantize+pack pass over the planar buffer (2R+W/4 more). One read of
    (y, v, g, x), one write of (y', v', words): the wire buffer never
    costs its own trip over the model. eta/theta ride a runtime [1, 2]
    scalar block like ``momentum_sgd``'s.
    """
    per = 32 // bits
    qmin = -(2 ** (bits - 1))
    qmax = 2 ** (bits - 1) - 1
    eta = et_ref[0, 0]
    theta = et_ref[0, 1]
    v_next = (theta * v_ref[...].astype(jnp.float32)
              - eta * g_ref[...].astype(jnp.float32))
    y_next = y_ref[...].astype(jnp.float32) + v_next
    delta = y_next - x_ref[...].astype(jnp.float32)
    s = block_scale(s_ref, 0)
    a = delta / s                            # [per, LANE_BLOCK] f32
    k = jnp.floor(a)
    if stochastic:
        k = k + (noise_ref[...] < (a - k)).astype(jnp.float32)
    k = jnp.clip(k, qmin, qmax).astype(jnp.int32)
    fields = (k + (1 << (bits - 1))).astype(jnp.uint32)
    y_out[...] = y_next.astype(y_out.dtype)
    v_out[...] = v_next.astype(v_out.dtype)
    w_out[...] = _pack_rows(fields, bits)


@functools.partial(jax.jit,
                   static_argnames=("bits", "stochastic", "interpret"))
def momentum_quantize_pack_buffer_pallas(
        y2d: jnp.ndarray, v2d: jnp.ndarray, g2d: jnp.ndarray,
        x2d: jnp.ndarray, s_blocks: jnp.ndarray, noise: jnp.ndarray,
        et: jnp.ndarray, *, bits: int, stochastic: bool,
        interpret: bool = False
        ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused-round encoder: the final applied local step and the whole
    planar wire buffer in ONE ``pallas_call``.

    y2d/v2d/g2d/x2d: [per, W] f32 planar buffers (y, v of the last applied
    step's inputs; g its gradient; x the round's held params); s_blocks:
    f32 [1, W // LANE_BLOCK] per-lane-block scales of the RESULTING delta
    (computed by the caller from the same expression — a reduction, not a
    full-size write); noise: [per, W] (ignored unless stochastic); et: f32
    [2] = (eta, theta), runtime (traced OK). Returns (y' [per, W],
    v' [per, W], words uint32 [W]). Pack math and layout are identical to
    :func:`quantize_pack_buffer_pallas`; the oracle is
    ``kernels.ref.momentum_quantize_pack_buffer_ref``.
    """
    per, w = y2d.shape
    assert per == 32 // bits and w % LANE_BLOCK == 0, (per, w)
    n_blocks = w // LANE_BLOCK
    assert s_blocks.shape == (1, n_blocks), (s_blocks.shape, n_blocks)
    kernel = functools.partial(_momentum_quantize_pack_kernel, bits=bits,
                               stochastic=stochastic)
    buf = pl.BlockSpec((per, LANE_BLOCK), lambda i: (0, i))
    y_o, v_o, words = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            buf, buf, buf, buf, buf,
            scale_spec(1),
            pl.BlockSpec((1, 2), lambda i: (0, 0)),
        ],
        out_specs=(buf, buf, _WORD_SPEC),
        out_shape=(jax.ShapeDtypeStruct(y2d.shape, y2d.dtype),
                   jax.ShapeDtypeStruct(v2d.shape, v2d.dtype),
                   jax.ShapeDtypeStruct((1, w), jnp.uint32)),
        interpret=interpret,
        name="momentum_quantize_pack_buffer",
    )(y2d, v2d, g2d, x2d, noise, scale_groups(s_blocks),
      et.reshape(1, 2).astype(jnp.float32))
    return y_o, v_o, words[0]


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
