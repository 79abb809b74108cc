"""Pallas TPU kernel: fused heavy-ball update (paper eq. 4, velocity form).

    v' = theta * v - eta * g
    y' = y + v'

Runs K times per communication round on every parameter — the elementwise
hot loop of local training. Unfused, XLA would emit separate HBM traffic
for the intermediate; fused we read (y, v, g) once and write (y', v')
once: 3 reads + 2 writes of N elements, the bandwidth floor.

``eta``/``theta`` are RUNTIME scalar operands (a tiny [1, 2] f32 block),
not compile-time constants: traced per-client learning rates — the async
engine's staleness-adaptive eta — run the same kernel without a retrace
or an XLA fallback, and a vmap over clients batches the scalar block like
any other operand.

Grid: 2-D over (row blocks, lane blocks) of a [R, C] view. Ragged shapes
are padded up to (ROW_BLOCK, LANE_BLOCK) multiples inside the wrapper and
sliced back after — zero-padding is a fixed point of the update (v' and
y' stay 0), so small paper-net configs take the fused path unchanged.
VMEM per step: 5 blocks of ROW_BLOCK x LANE_BLOCK f32 = 5*8*512*4 ≈ 80 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_BLOCK = 8
LANE_BLOCK = 512


def _momentum_kernel(y_ref, v_ref, g_ref, et_ref, y_out, v_out):
    eta = et_ref[0, 0]
    theta = et_ref[0, 1]
    v_next = (theta * v_ref[...].astype(jnp.float32)
              - eta * g_ref[...].astype(jnp.float32))
    y_out[...] = (y_ref[...].astype(jnp.float32) + v_next).astype(y_out.dtype)
    v_out[...] = v_next.astype(v_out.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def momentum_sgd_pallas(y2d: jnp.ndarray, v2d: jnp.ndarray, g2d: jnp.ndarray,
                        *, eta, theta, interpret: bool = False
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """All inputs [R, C]; any R, C — ragged shapes are zero-padded to the
    (ROW_BLOCK, LANE_BLOCK) grid and sliced back. ``eta``/``theta`` may be
    python floats or traced f32 scalars (runtime operands)."""
    r, c = y2d.shape
    rp = -(-r // ROW_BLOCK) * ROW_BLOCK
    cp = -(-c // LANE_BLOCK) * LANE_BLOCK
    padded = (rp, cp) != (r, c)
    if padded:
        pad = ((0, rp - r), (0, cp - c))
        y2d, v2d, g2d = (jnp.pad(a, pad) for a in (y2d, v2d, g2d))
    et = jnp.stack([jnp.asarray(eta, jnp.float32),
                    jnp.asarray(theta, jnp.float32)]).reshape(1, 2)
    grid = (rp // ROW_BLOCK, cp // LANE_BLOCK)
    spec = pl.BlockSpec((ROW_BLOCK, LANE_BLOCK), lambda i, j: (i, j))
    et_spec = pl.BlockSpec((1, 2), lambda i, j: (0, 0))
    y_o, v_o = pl.pallas_call(
        _momentum_kernel,
        grid=grid,
        in_specs=[spec, spec, spec, et_spec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct(y2d.shape, y2d.dtype),
                   jax.ShapeDtypeStruct(v2d.shape, v2d.dtype)),
        interpret=interpret,
        name="momentum_sgd",
    )(y2d, v2d, g2d, et)
    if padded:
        y_o, v_o = y_o[:r, :c], v_o[:r, :c]
    return y_o, v_o
