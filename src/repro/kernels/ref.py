"""Pure-jnp oracles for every Pallas kernel in this package.

Wire format used by the kernels (differs from core.quantize's sequential
packing; both are self-consistent pairs and the wire is opaque):

  *planar* packing — a flat tensor of n values is padded to ``per * W``
  (per = 32 // bits) and viewed as [per, W]; word w packs elements
  [0, w], [1, w], ..., [per-1, w]:

      word[w] = sum_i (offset_encode(x[i, w]) << (bits * i))

  This keeps every shift/or lane-parallel on the TPU vector unit (the
  lane axis W is a multiple of 128), instead of gathering 32/b adjacent
  elements within a lane.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LANE_BLOCK = 512  # lane-dim block for all kernels (multiple of 128)


def planar_pad_len(n: int, bits: int) -> tuple[int, int]:
    """Return (per, W) with per*W >= n, W a multiple of LANE_BLOCK."""
    per = 32 // bits
    w = -(-n // per)
    w = -(-w // LANE_BLOCK) * LANE_BLOCK
    return per, w


def quantize_pack_ref(x: jnp.ndarray, bits: int, s: jnp.ndarray,
                      noise: jnp.ndarray | None = None) -> jnp.ndarray:
    """Quantize flat f32 x (len n) with step s; planar-pack to uint32 [W].

    noise: uniform[0,1) of x.shape for stochastic rounding; None = floor.
    """
    n = x.shape[0]
    per, w = planar_pad_len(n, bits)
    qmin, qmax = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    a = x.astype(jnp.float32) / s
    k = jnp.floor(a)
    if noise is not None:
        k = k + (noise < (a - k)).astype(jnp.float32)
    k = jnp.clip(k, qmin, qmax).astype(jnp.int32)
    k = jnp.pad(k, (0, per * w - n))
    fields = (k + (1 << (bits - 1))).astype(jnp.uint32).reshape(per, w)
    shifts = (jnp.arange(per, dtype=jnp.uint32) * bits)[:, None]
    return (fields << shifts).sum(axis=0, dtype=jnp.uint32)


def unpack_dequant_ref(words: jnp.ndarray, bits: int, s: jnp.ndarray,
                       n: int) -> jnp.ndarray:
    """Inverse of quantize_pack_ref (up to the quantization itself)."""
    per = 32 // bits
    w = words.shape[0]
    shifts = (jnp.arange(per, dtype=jnp.uint32) * bits)[:, None]
    mask = jnp.uint32((1 << bits) - 1)
    fields = (words[None, :] >> shifts) & mask
    k = fields.astype(jnp.int32) - (1 << (bits - 1))
    return (k.astype(jnp.float32) * s).reshape(per * w)[:n]


def _per_block(x: jnp.ndarray, block_scales: jnp.ndarray, op) -> jnp.ndarray:
    """``op(x, scale of x's lane block)`` elementwise: x [..., per, W],
    block_scales [..., W // LANE_BLOCK]. The lane axis is split into
    (blocks, LANE_BLOCK) instead of repeating the scales to [..., W]:
    the TPU compiler takes minutes on that repeat at model width and
    about a second on this form, and the values are the same."""
    shp = x.shape
    xb = x.reshape(shp[:-1] + (shp[-1] // LANE_BLOCK, LANE_BLOCK))
    return op(xb, block_scales[..., None, :, None]).reshape(shp)


def quantize_pack_buffer_ref(x: jnp.ndarray, block_scales: jnp.ndarray,
                             bits: int,
                             noise: jnp.ndarray | None = None
                             ) -> jnp.ndarray:
    """Whole-buffer quantize + planar pack with PER-LANE-BLOCK scales (the
    flat wire path: each ``LANE_BLOCK``-word block carries its owning
    leaf's scale — see ``core.wire_layout.WireLayout``).

    x: [..., per, W] f32 (W % LANE_BLOCK == 0); block_scales:
    [..., W // LANE_BLOCK] f32; noise: uniform[0,1) like x for stochastic
    rounding, None = deterministic floor. Returns uint32 [..., W].

    This is both the CPU execution path of the flat codec and the
    bit-exactness oracle for ``quantize_pack_buffer_pallas``.
    """
    per = 32 // bits
    qmin, qmax = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    a = _per_block(x.astype(jnp.float32), block_scales.astype(jnp.float32),
                   jnp.divide)
    k = jnp.floor(a)
    if noise is not None:
        k = k + (noise < (a - k)).astype(jnp.float32)
    k = jnp.clip(k, qmin, qmax).astype(jnp.int32)
    fields = (k + (1 << (bits - 1))).astype(jnp.uint32)
    shifts = (jnp.arange(per, dtype=jnp.uint32) * bits)[:, None]
    return (fields << shifts).sum(axis=-2, dtype=jnp.uint32)


def dequant_mix_buffer_ref(base: jnp.ndarray, streams: jnp.ndarray,
                           block_scales: jnp.ndarray, weights: jnp.ndarray,
                           bits: int) -> jnp.ndarray:
    """Whole-buffer fused unpack + dequantize + weighted apply:

        out = base + sum_k weights[..., k] * deq(streams[..., k, :])

    base: [..., per, W]; streams: uint32 [..., K, W]; block_scales:
    [..., K, W // LANE_BLOCK]; weights: [..., K] (traced OK — the
    per-round gathered mask). CPU path + oracle of
    ``dequant_mix_buffer_pallas``; the accumulation order (own stream
    first, then plan steps) matches the kernel exactly.

    Bitwise caveat: the integer unpack and the VALUES fed into the
    accumulation are exact, but XLA may contract each multiply-add into
    an FMA depending on the surrounding fusion, so two compilations of
    this accumulation can differ by ~1 ulp per term. The flat wire path
    therefore guarantees a BITWISE wire (words + scales) and a
    few-ulp-reproducible fused output — never bitwise float equality
    across independently compiled modules.
    """
    per = 32 // bits
    n_streams = streams.shape[-2]
    mask = jnp.uint32((1 << bits) - 1)
    offset = 1 << (bits - 1)
    shifts = (jnp.arange(per, dtype=jnp.uint32) * bits)[:, None]
    bs = block_scales.astype(jnp.float32)
    acc = base.astype(jnp.float32)
    for k in range(n_streams):
        fields = (streams[..., k, None, :] >> shifts) & mask
        deq = _per_block((fields.astype(jnp.int32) - offset)
                         .astype(jnp.float32), bs[..., k, :], jnp.multiply)
        acc = acc + weights[..., k, None, None] * deq
    return acc.astype(base.dtype)


def momentum_quantize_pack_buffer_ref(y: jnp.ndarray, v: jnp.ndarray,
                                      g: jnp.ndarray, x: jnp.ndarray,
                                      block_scales: jnp.ndarray, bits: int,
                                      eta, theta,
                                      noise: jnp.ndarray | None = None
                                      ) -> tuple[jnp.ndarray, jnp.ndarray,
                                                 jnp.ndarray]:
    """Fused final-local-step + whole-buffer encode (oracle + CPU path of
    ``momentum_quantize_pack_buffer_pallas``):

        v' = theta*v - eta*g ;  y' = y + v' ;  words = pack(Q(y' - x))

    y/v/g/x: [..., per, W] f32 planar buffers; block_scales:
    [..., W // LANE_BLOCK] f32 — scales of the RESULTING delta, computed by
    the caller from the same expression order; eta/theta: scalars (traced
    OK). Returns (y', v', words [..., W]). The pack math is
    ``quantize_pack_buffer_ref`` verbatim; the update expression order
    matches the kernel so the integer wire stays the oracle's.
    """
    eta = jnp.asarray(eta, jnp.float32)
    theta = jnp.asarray(theta, jnp.float32)
    v_next = theta * v.astype(jnp.float32) - eta * g.astype(jnp.float32)
    y_next = y.astype(jnp.float32) + v_next
    delta = y_next - x.astype(jnp.float32)
    words = quantize_pack_buffer_ref(delta, block_scales, bits, noise)
    return y_next.astype(y.dtype), v_next.astype(v.dtype), words


def dequant_mix_momentum_buffer_ref(base: jnp.ndarray, streams: jnp.ndarray,
                                    block_scales: jnp.ndarray,
                                    weights: jnp.ndarray, v: jnp.ndarray,
                                    g: jnp.ndarray, et: jnp.ndarray,
                                    bits: int) -> jnp.ndarray:
    """Fused mix + deferred momentum (oracle + CPU path of
    ``dequant_mix_momentum_buffer_pallas``):

        out = [base + sum_k weights[..., k] * deq(streams[..., k, :])]
              + (theta*v - eta*g)

    Shapes as in ``dequant_mix_buffer_ref`` plus v/g: [..., per, W] and
    et: f32 [..., 2] = (eta, theta). The momentum term is added to the f32
    accumulator BEFORE the output-dtype cast — same op order as the
    kernel; the FMA-contraction bitwise caveat of
    ``dequant_mix_buffer_ref`` applies unchanged.
    """
    per = 32 // bits
    n_streams = streams.shape[-2]
    mask = jnp.uint32((1 << bits) - 1)
    offset = 1 << (bits - 1)
    shifts = (jnp.arange(per, dtype=jnp.uint32) * bits)[:, None]
    bs = block_scales.astype(jnp.float32)
    acc = base.astype(jnp.float32)
    for k in range(n_streams):
        fields = (streams[..., k, None, :] >> shifts) & mask
        deq = _per_block((fields.astype(jnp.int32) - offset)
                         .astype(jnp.float32), bs[..., k, :], jnp.multiply)
        acc = acc + weights[..., k, None, None] * deq
    et = jnp.asarray(et, jnp.float32)
    v_next = (et[..., 1, None, None] * v.astype(jnp.float32)
              - et[..., 0, None, None] * g.astype(jnp.float32))
    return (acc + v_next).astype(base.dtype)


def dequant_mix_ref(x: jnp.ndarray, q_own: jnp.ndarray, q_left: jnp.ndarray,
                    q_right: jnp.ndarray, scales: jnp.ndarray, bits: int,
                    w_self: float, w_nb: float) -> jnp.ndarray:
    """Fused eq.-7 ring update for one client:

        x + w_self * deq(q_own) + w_nb * deq(q_left) + w_nb * deq(q_right)

    x: flat f32 [n]; q_*: packed uint32 [W]; scales: f32 [3] (own, left,
    right).
    """
    n = x.shape[0]
    d_own = unpack_dequant_ref(q_own, bits, scales[0], n)
    d_l = unpack_dequant_ref(q_left, bits, scales[1], n)
    d_r = unpack_dequant_ref(q_right, bits, scales[2], n)
    return (x.astype(jnp.float32)
            + w_self * d_own + w_nb * d_l + w_nb * d_r).astype(x.dtype)


def momentum_sgd_ref(y: jnp.ndarray, v: jnp.ndarray, g: jnp.ndarray,
                     eta: float, theta: float
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Heavy-ball (paper eq. 4, velocity form):
        v' = theta*v - eta*g ;  y' = y + v'
    """
    v_next = theta * v.astype(jnp.float32) - eta * g.astype(jnp.float32)
    y_next = y.astype(jnp.float32) + v_next
    return y_next.astype(y.dtype), v_next.astype(v.dtype)
