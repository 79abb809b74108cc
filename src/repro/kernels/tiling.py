"""Lane tiles of the codec kernels (``quantize_pack``, ``dequant_mix``).

A planar wire buffer is [rows, W], W a multiple of LANE_BLOCK, with one
scale per LANE_BLOCK-word lane block. A grid step covers a TILE of G
consecutive lane blocks, so a model-wide buffer streams in
cdiv(n_blocks, G) steps instead of n_blocks: a per-step cost of about a
quarter of a microsecond kept a one-block step near a tenth of the HBM
bandwidth. The kernel body walks its tile one lane block at a time
(:func:`for_each_block`), each block with its own scale, so the
arithmetic and the wire are those of a one-block grid.

G is the largest power of two up to TILE_BLOCKS whose double-buffered
in and out blocks fit VMEM_BUDGET, and n_blocks where that is fewer (one
grid step). A ragged last step reads past the buffer's end; its
out-of-range lane blocks are computed from padding and dropped on write.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import LANE_BLOCK

TILE_BLOCKS = 64            # most lane blocks a step: 32K words
VMEM_BUDGET = 8 << 20       # a step's blocks, double-buffered (v5e: 16 MiB)

# Per-lane-block scales reach the kernels through SMEM, SCALE_GROUP blocks
# at a time: a (rows, G) block of a [rows, n_blocks] array is no legal TPU
# block unless G is a multiple of 128 or the whole array, so one scale
# block serves SCALE_GROUP // G whole grid steps.
SCALE_GROUP = 128
assert SCALE_GROUP % TILE_BLOCKS == 0


@dataclasses.dataclass(frozen=True)
class LaneTiles:
    """The tiling of one ``pallas_call`` over ``n_blocks`` lane blocks."""
    n_blocks: int
    g: int                  # lane blocks a grid step

    @property
    def grid(self) -> tuple[int]:
        return (pl.cdiv(self.n_blocks, self.g),)

    def spec(self, rows: int) -> pl.BlockSpec:
        """A (rows, G * LANE_BLOCK) tile of a [rows, W] operand."""
        return pl.BlockSpec((rows, self.g * LANE_BLOCK), lambda i: (0, i))

    def scale_spec(self, rows: int) -> pl.BlockSpec:
        """SMEM block of ``rows`` x SCALE_GROUP per-block scales that holds
        this step's G (pair it with :func:`scale_groups`)."""
        g = self.g
        return pl.BlockSpec((rows, SCALE_GROUP),
                            lambda i: (0, i * g // SCALE_GROUP),
                            memory_space=pltpu.SMEM)


def lane_tiles(n_blocks: int, *tiled) -> LaneTiles:
    """Tiles for a call whose lane-tiled operands and outputs are
    ``tiled`` (arrays or ``ShapeDtypeStruct``s of [rows, W]): their rows
    and dtypes set the bytes a lane block moves, hence G."""
    lane_bytes = sum(a.shape[0] * jnp.dtype(a.dtype).itemsize for a in tiled)
    g = TILE_BLOCKS
    while g > 1 and 2 * g * LANE_BLOCK * lane_bytes > VMEM_BUDGET:
        g //= 2
    return LaneTiles(n_blocks, min(g, n_blocks))


def scale_groups(block_scales: jnp.ndarray) -> jnp.ndarray:
    """f32 [rows, n_blocks] -> [rows, n_blocks rounded up to SCALE_GROUP]
    (padded with ones: only a ragged last step reads the padding, for lane
    blocks that are never written)."""
    pad = -block_scales.shape[-1] % SCALE_GROUP
    return jnp.pad(block_scales.astype(jnp.float32), ((0, 0), (0, pad)),
                   constant_values=1.0)


def for_each_block(g: int, s_ref, body) -> None:
    """Run ``body(lanes, scale)`` on each of this step's G lane blocks:
    ``lanes`` slices the block out of a tile, ``scale(row)`` reads its
    scale in row ``row`` of a :meth:`LaneTiles.scale_spec` block."""
    first = pl.program_id(0) * g

    def step(j, carry):
        lanes = pl.ds(pl.multiple_of(j * LANE_BLOCK, LANE_BLOCK), LANE_BLOCK)
        col = (first + j) % SCALE_GROUP
        body(lanes, lambda row: s_ref[row, col])
        return carry

    # Unrolled: a rolled loop left the decode at a fifth of the HBM
    # bandwidth on a v5e (Mosaic unrolls fully or not at all).
    jax.lax.fori_loop(0, g, step, 0, unroll=True)
