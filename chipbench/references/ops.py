"""Plain float32 building blocks shared by the reference models.

Every contraction goes through one :class:`Matmul`, so the same model
code runs as the reference (float32 operands, ``precision=HIGHEST``) and
as its control (operands rounded to float8 e4m3 with a per-tensor scale
before the float32 contraction: the lower precision a later change
might be tempted to take). Nothing here imports the program.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

F8_MAX = 448.0   # largest finite float8_e4m3fn


@dataclasses.dataclass(frozen=True)
class Matmul:
    """``fp8=False``: float32 at ``HIGHEST`` precision (on a TPU the
    default float32 matmul is one bfloat16 pass). ``fp8=True``: each
    operand is scaled by ``448 / max|operand|``, rounded to
    float8_e4m3fn and scaled back before the same float32 contraction."""

    fp8: bool = False

    def _round(self, a):
        a = a.astype(jnp.float32)
        if not self.fp8:
            return a
        amax = jnp.max(jnp.abs(a))
        s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
        return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s

    def __call__(self, spec: str, a, b):
        return jnp.einsum(spec, self._round(a), self._round(b),
                          precision=jax.lax.Precision.HIGHEST)


def layer_norm(x, eps: float = 1e-5):
    """LayerNorm without scale or bias (OLMo's non-parametric LN)."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def rms_norm(x, scale, eps: float = 1e-5):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1,
                                 keepdims=True) + eps) * scale


def next_token_nll(mm: Matmul, h, table, targets):
    """Mean next-token cross-entropy of one sequence with a tied
    embedding ``table`` [V, d] as the output head. h [l, d]."""
    logits = mm("ld,vd->lv", h, table)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - tgt)


def normal(key, shape, fan_in, dtype):
    """Gaussian weight with standard deviation ``1/sqrt(fan_in)``, made
    in float32 and stored in ``dtype``."""
    return (jax.random.normal(key, shape, jnp.float32)
            / jnp.sqrt(jnp.float32(fan_in))).astype(dtype)
