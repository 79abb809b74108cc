"""Flat wire-buffer layout for the sparse gossip hot loop.

The per-leaf wire path paid the quantized-gossip overhead once PER LEAF
PER PLAN STEP: encode, two collective launches (words + scale), unpack,
dequantize — so the communication-optimal backend was compute-pessimal
(BENCH_gossip.json: sparse q8 moved ~14x fewer bytes than dense q8 yet
ran ~5x slower). A :class:`WireLayout` removes the per-leaf axis from the
hot loop entirely: the client-local model pytree is flattened ONCE into a
single planar ``[per, W]`` buffer (``per = 32 // bits``, lane axis ``W`` a
multiple of ``LANE_BLOCK``), each leaf occupying a block-aligned column
segment. Quantize/pack, the per-step ``ppermute``, and the fused
dequantize/mix then each run once per round on one contiguous array:

  flatten -> quantize/pack (one kernel) -> ppermute (one collective per
  plan step; per-leaf scales ride in the u32 stream tail) -> fused
  dequant-mix (one kernel over all received streams).

Numerics are unchanged: scales stay PER LEAF (segment max-abs, the same
``amax / qmax`` formula as ``core.quantize._scale_for``), and stochastic
rounding draws the same per-leaf, per-client bits as the dense reference
(``uniform(key_leaf_client, (n,))``, zero-padded — padding never rounds
up).

Invariants (pinned by ``tests/test_wire_layout.py``):

  * LANE-ALIGNED SEGMENTS: every leaf's column segment starts on a
    ``LANE_BLOCK`` boundary of the planar buffer, so the Pallas kernels
    tile it without cross-leaf reads and the XLA reference slices it
    without gather ops; round-tripping ``to_planar``/``from_planar`` is
    exact for every dtype.
  * PER-LEAF SCALES: one scale per (client, leaf), identical to the
    dense path's ``_scale_for`` — the flat layout changes memory
    traffic, never numerics.
  * Padding encodes to 0 words and never rounds up, so two models that
    differ only in alignment padding put identical bits on the wire.

The codec has two interchangeable backends: the Pallas buffer
kernels (``kernels.quantize_pack`` / ``kernels.dequant_mix``, selected on
TPU) and a pure-XLA reference (CPU default, and the kernels' parity
oracle: the integer WIRE — packed words and scales — is bit-identical
between them, and the fused float apply agrees to a few ulp, since XLA
picks FMA contraction per compiled module).

On a 2D ``(clients, model)`` mesh the layout gains a model-shard
dimension implicitly: the mixer's shard_map body sees only this device's
model slice of every leaf, so ``for_tree`` of the LOCAL tree already
yields per-shard lane-aligned segments and a ``total_words`` that shrinks
~linearly with model parallelism (which is exactly what each boundary
ppermute ships). Two hooks keep the sharded wire bitwise-consistent with
the 1D layout: :meth:`leaf_amax` exposes the pre-scale reduction so the
executor can ``lax.pmax`` it across model shards (max is order-exact —
every shard derives the identical per-leaf scale), and
``encode(noise=...)`` accepts externally drawn rounding noise, sliced
from the FULL leaf's draw in leaf geometry so each shard replays the 1D
PRNG stream at its own positions.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.ref import LANE_BLOCK

Pytree = Any

__all__ = ["WireLayout", "LANE_BLOCK", "rows_to_planar"]


def rows_to_planar(flat: jnp.ndarray, per: int, lw: int) -> jnp.ndarray:
    """[..., n] -> [..., per, lw], zero-padded: row r holds
    ``flat[..., r*lw:(r+1)*lw]`` — the planar view of a flat vector. It
    is built from row slices because the TPU compiler takes minutes per
    leaf on the equivalent pad + reshape at model width (splitting the
    minor dim), and about a second this way."""
    n = flat.shape[-1]
    pad = [(0, 0)] * (flat.ndim - 1)
    rows = []
    for r in range(per):
        row = flat[..., r * lw:min((r + 1) * lw, n)]
        rows.append(jnp.pad(row, pad + [(0, lw - row.shape[-1])]))
    return jnp.stack(rows, axis=-2)


def _planar_to_leaf(buf: jnp.ndarray, shape: tuple) -> jnp.ndarray:
    """Inverse of :func:`rows_to_planar` into a leaf's shape: [..., per,
    lw] -> [..., *shape], by concatenating rows (see there for why not a
    reshape). Where a row holds whole rows of the leaf's 2-D view, the
    rows are joined in that view: joining them flat and reshaping the
    result takes the TPU compiler about a minute per layer weight."""
    lead, lw = buf.shape[:-2], buf.shape[-1]
    n = int(np.prod(shape)) if shape else 1
    last = shape[-1] if shape else 1
    n_rows = -(-n // lw)
    if lw % last == 0:
        rows = [buf[..., r, :].reshape(lead + (lw // last, last))
                for r in range(n_rows)]
        flat2d = jnp.concatenate(rows, axis=-2)[..., :n // last, :]
        return flat2d.reshape(lead + tuple(shape))
    rows = [buf[..., r, :] for r in range(n_rows)]
    return jnp.concatenate(rows, axis=-1)[..., :n].reshape(
        lead + tuple(shape))


@dataclasses.dataclass(frozen=True)
class WireLayout:
    """Planar layout of one client's parameter pytree on the wire.

    Leaf ``i`` (flat size ``sizes[i]``) occupies columns
    ``[word_offsets[i], word_offsets[i] + leaf_words[i])`` of the
    ``[per, total_words]`` buffer; ``leaf_words[i]`` is padded up to a
    multiple of ``LANE_BLOCK`` so every lane block belongs to exactly one
    leaf (``block_leaf`` maps block -> leaf, which is how per-leaf scales
    become the kernels' per-block scales). The planar view of a leaf is
    just the zero-padded flat vector reshaped to ``[per, leaf_words]`` —
    identical element order to the sequential codec, so quantization
    decisions are positionwise the same.
    """

    treedef: Any
    shapes: tuple
    dtypes: tuple
    bits: int | None            # None: fp32 wire (no planar geometry)
    sizes: tuple
    per: int
    leaf_words: tuple
    word_offsets: tuple
    total_words: int
    block_leaf: np.ndarray      # [total_words // LANE_BLOCK] int32

    @staticmethod
    def for_tree(tree: Pytree, bits: int | None = None) -> "WireLayout":
        """Build the layout from a CLIENT-LOCAL tree (leaves without the
        stacked client axis); only shapes/dtypes are read, so abstract
        values work too."""
        leaves, treedef = jax.tree.flatten(tree)
        shapes = tuple(tuple(l.shape) for l in leaves)
        dtypes = tuple(jnp.asarray(l).dtype if not hasattr(l, "dtype")
                       else l.dtype for l in leaves)
        sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
        if bits is None:
            return WireLayout(treedef=treedef, shapes=shapes, dtypes=dtypes,
                              bits=None, sizes=sizes, per=1,
                              leaf_words=sizes,
                              word_offsets=tuple(np.cumsum((0,) + sizes[:-1])
                                                 .tolist()),
                              total_words=int(sum(sizes)),
                              block_leaf=np.zeros((0,), np.int32))
        per = 32 // bits

        def aligned_words(n: int) -> int:
            w = -(-n // per)                       # ceil(n / per)
            return -(-w // LANE_BLOCK) * LANE_BLOCK

        lw = tuple(aligned_words(n) for n in sizes)
        offs = tuple(np.cumsum((0,) + lw[:-1]).tolist())
        total = int(sum(lw))
        block_leaf = np.repeat(np.arange(len(sizes), dtype=np.int32),
                               [w // LANE_BLOCK for w in lw])
        return WireLayout(treedef=treedef, shapes=shapes, dtypes=dtypes,
                          bits=bits, sizes=sizes, per=per, leaf_words=lw,
                          word_offsets=offs, total_words=total,
                          block_leaf=block_leaf)

    @property
    def n_leaves(self) -> int:
        return len(self.sizes)

    @property
    def n_blocks(self) -> int:
        return self.total_words // LANE_BLOCK

    def _leaves(self, tree: Pytree) -> list:
        leaves = self.treedef.flatten_up_to(tree)
        if len(leaves) != self.n_leaves:
            raise ValueError("tree does not match layout")
        return leaves

    # -- fp32 wire: plain flatten/unflatten ---------------------------------

    def flatten_f32(self, tree: Pytree) -> jnp.ndarray:
        """Client-local tree -> flat f32 [sum(sizes)] (fp32 wire)."""
        return jnp.concatenate(
            [l.reshape(-1).astype(jnp.float32) for l in self._leaves(tree)])

    def unflatten(self, flat: jnp.ndarray) -> Pytree:
        outs, off = [], 0
        for shape, dtype, n in zip(self.shapes, self.dtypes, self.sizes):
            outs.append(flat[off:off + n].reshape(shape).astype(dtype))
            off += n
        return jax.tree.unflatten(self.treedef, outs)

    # -- planar (quantized) wire --------------------------------------------

    def to_planar(self, tree: Pytree) -> jnp.ndarray:
        """Client-local tree -> [per, total_words] f32, zero-padded."""
        segs = [rows_to_planar(leaf.reshape(-1).astype(jnp.float32),
                               self.per, lw)
                for leaf, lw in zip(self._leaves(tree), self.leaf_words)]
        return jnp.concatenate(segs, axis=1)

    def from_planar(self, buf2d: jnp.ndarray) -> Pytree:
        outs = []
        for shape, dtype, lw, off in zip(self.shapes, self.dtypes,
                                         self.leaf_words, self.word_offsets):
            outs.append(_planar_to_leaf(buf2d[:, off:off + lw], shape)
                        .astype(dtype))
        return jax.tree.unflatten(self.treedef, outs)

    def to_planar_stacked(self, tree: Pytree) -> jnp.ndarray:
        """Stacked tree (leaves [m, ...]) -> [m, per, total_words] f32.
        Row c equals ``to_planar`` of client c's local tree — the batched
        form the mesh-free reference executor uses."""
        segs = [rows_to_planar(
                    leaf.reshape(leaf.shape[0], -1).astype(jnp.float32),
                    self.per, lw)
                for leaf, lw in zip(self._leaves(tree), self.leaf_words)]
        return jnp.concatenate(segs, axis=2)

    def from_planar_stacked(self, buf: jnp.ndarray) -> Pytree:
        outs = []
        for shape, dtype, lw, off in zip(self.shapes, self.dtypes,
                                         self.leaf_words, self.word_offsets):
            outs.append(_planar_to_leaf(buf[:, :, off:off + lw], shape)
                        .astype(dtype))
        return jax.tree.unflatten(self.treedef, outs)

    # -- per-leaf scales and stochastic-rounding noise ----------------------

    def leaf_amax(self, delta: jnp.ndarray) -> jnp.ndarray:
        """Per-leaf ``max|x|`` of a planar delta buffer (leading batch dims
        allowed). [..., n_leaves]. Split out from :meth:`leaf_scales` so a
        model-sharded layout can all-reduce the LOCAL amaxes over the model
        axis (``lax.pmax``) before turning them into scales — max is
        order-exact, so the resulting scales are bitwise identical to the
        unsharded layout's."""
        amaxs = []
        for lw, off in zip(self.leaf_words, self.word_offsets):
            amaxs.append(jnp.max(jnp.abs(delta[..., :, off:off + lw]),
                                 axis=(-2, -1)))
        return jnp.stack(amaxs, axis=-1)

    def scales_from_amax(self, amax: jnp.ndarray, quant) -> jnp.ndarray:
        """Per-leaf amaxes [..., n_leaves] -> quantizer steps, the same
        ``s = amax / qmax`` (0 -> 1.0) as ``core.quantize._scale_for``."""
        if quant.scale_mode == "fixed":
            return jnp.full(amax.shape, quant.s, jnp.float32)
        from .quantize import scale_from_amax
        s = scale_from_amax(amax, quant.qmax)
        return jnp.where(s > 0, s, jnp.float32(1.0))

    def leaf_scales(self, delta: jnp.ndarray, quant) -> jnp.ndarray:
        """Per-leaf quantizer steps of a planar delta buffer (leading batch
        dims allowed): the same ``s = max|x| / qmax`` (0 -> 1.0) as
        ``core.quantize._scale_for``, per leaf segment. [..., n_leaves]."""
        if quant.scale_mode == "fixed":
            batch = delta.shape[:-2]
            return jnp.full(batch + (self.n_leaves,), quant.s, jnp.float32)
        return self.scales_from_amax(self.leaf_amax(delta), quant)

    def noise(self, leaf_keys: jnp.ndarray) -> jnp.ndarray:
        """Stochastic-rounding noise for one client: ``leaf_keys``
        [n_leaves, 2] uint32 (one PRNG key per leaf — the shared
        ``_quant_leaf_keys`` derivation, so the dense reference draws the
        IDENTICAL bits). Padding is zero: ``noise < (a - floor(a))`` never
        rounds a padded zero up. Returns [per, total_words]."""
        segs = [rows_to_planar(
                    jax.random.uniform(leaf_keys[li], (n,), jnp.float32),
                    self.per, lw)
                for li, (n, lw) in enumerate(zip(self.sizes,
                                                 self.leaf_words))]
        return jnp.concatenate(segs, axis=1)

    def noise_stacked(self, keys: jnp.ndarray) -> jnp.ndarray:
        """Batched :meth:`noise`: ``keys`` [n_leaves, m, 2] (the raw
        ``_quant_leaf_keys`` output) -> [m, per, total_words]."""
        segs = [rows_to_planar(
                    jax.vmap(lambda k, n=n: jax.random.uniform(
                        k, (n,), jnp.float32))(keys[li]), self.per, lw)
                for li, (n, lw) in enumerate(zip(self.sizes,
                                                 self.leaf_words))]
        return jnp.concatenate(segs, axis=2)

    def block_scales(self, scales: jnp.ndarray) -> jnp.ndarray:
        """Per-leaf scales [..., n_leaves] -> per-lane-block scales
        [..., n_blocks] (what the buffer kernels consume)."""
        return scales[..., self.block_leaf]

    # -- codec dispatch -----------------------------------------------------

    @jax.named_scope("wire/encode")
    def encode(self, delta: jnp.ndarray, scales: jnp.ndarray, quant,
               leaf_keys=None, pallas: bool = False,
               noise=None) -> jnp.ndarray:
        """Quantize + planar-pack the whole buffer in one pass.

        delta [per, W] f32 (pallas) or [..., per, W] (xla); scales
        [..., n_leaves]. ``noise`` overrides the internal per-leaf draw
        with precomputed rounding noise in planar geometry (the 2D-mesh
        path slices the FULL leaf's draw to its model shard outside the
        layout, where the unsharded leaf geometry is known). Returns
        packed uint32 words [..., W].
        """
        from ..kernels import ref as kref
        sblk = self.block_scales(scales)
        stochastic = bool(quant.stochastic)
        if stochastic and noise is None:
            if leaf_keys is None:
                raise ValueError("stochastic encode needs per-leaf keys")
            with jax.named_scope("noise"):
                noise = (self.noise(leaf_keys) if delta.ndim == 2
                         else self.noise_stacked(leaf_keys))
        elif not stochastic:
            noise = None
        if pallas:
            from ..kernels.ops import default_interpret
            from ..kernels.quantize_pack import quantize_pack_buffer_pallas
            nz = noise if noise is not None else jnp.zeros_like(delta)
            if delta.ndim == 3:
                # Block-sharded lane axis: lax.map one kernel launch per
                # local client at the m_local == 1 shapes — the HLO
                # carries ONE traced body regardless of m_local (a
                # Python unroll would trace m_local copies).
                return jax.lax.map(
                    lambda a: quantize_pack_buffer_pallas(
                        a[0], a[1].reshape(1, -1), a[2], bits=quant.bits,
                        stochastic=stochastic,
                        interpret=default_interpret()),
                    (delta, sblk, nz))
            return quantize_pack_buffer_pallas(
                delta, sblk.reshape(1, -1), nz, bits=quant.bits,
                stochastic=stochastic, interpret=default_interpret())
        return kref.quantize_pack_buffer_ref(delta, sblk, quant.bits,
                                             noise=noise)

    @jax.named_scope("wire/decode")
    def decode_apply(self, base: jnp.ndarray, streams: jnp.ndarray,
                     scales: jnp.ndarray, weights: jnp.ndarray, quant,
                     pallas: bool = False) -> jnp.ndarray:
        """Fused ``base + sum_k weights[k] * deq(streams[k], scales[k])``
        over the whole buffer: base [..., per, W]; streams uint32
        [..., k, W]; scales [..., k, n_leaves]; weights [..., k]."""
        sblk = self.block_scales(scales)
        if pallas:
            from ..kernels.dequant_mix import dequant_mix_buffer_pallas
            from ..kernels.ops import default_interpret
            if base.ndim == 3:
                # Block-sharded lane axis: one traced per-lane kernel
                # body via lax.map (see encode above).
                return jax.lax.map(
                    lambda a: dequant_mix_buffer_pallas(
                        a[0], a[1], a[2], a[3], bits=quant.bits,
                        interpret=default_interpret()),
                    (base, streams, sblk, weights))
            return dequant_mix_buffer_pallas(
                base, streams, sblk, weights, bits=quant.bits,
                interpret=default_interpret())
        from ..kernels import ref as kref
        return kref.dequant_mix_buffer_ref(base, streams, sblk, weights,
                                           quant.bits)
