"""Pallas TPU kernels for the paper's communication/update hot spots.

quantize_pack — b-bit quantize + planar bit-pack (wire encoder, Alg. 2):
                per-tensor scale (``quantize_pack_pallas``) and the flat
                wire-buffer variant with per-lane-block segment scales
                (``quantize_pack_buffer_pallas`` — one call encodes the
                whole model, see ``core.wire_layout``)
dequant_mix   — fused unpack + dequantize + gossip apply (eq. 7): ring /
                plan-stream forms, and the whole-buffer
                ``dequant_mix_buffer_pallas`` consuming every received
                stream + runtime scales/weights in one pass
momentum_sgd  — fused heavy-ball parameter update (eq. 4)
tiling        — the codec kernels' grid: tiles of many lane blocks a step,
                each block with its own scale

Each kernel has a pure-jnp oracle in ``ref.py`` (the buffer oracles double
as the CPU execution path of the flat wire codec) and a padded/jit'd
wrapper in ``ops.py``; tests sweep shapes/dtypes in interpret mode
against ref.
"""
from .ops import (default_interpret, encode_delta, decode_apply_ring,  # noqa
                  decode_apply_plan, momentum_update_flat,
                  make_fused_momentum_update)
