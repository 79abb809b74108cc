"""Mamba2 (SSD — state-space duality) block [arXiv:2405.21060].

Faithful chunked SSD: intra-chunk quadratic (dual/attention) form + an
inter-chunk state recurrence (lax.scan), O(L * Q) instead of O(L^2);
single-step recurrence for decode with O(1) state:

  h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x)_t,   y_t = C_t . h_t + D x_t

TPU adaptation (noted in DESIGN.md): the reference CUDA impl fuses
(z, x, B, C, dt) into one in-projection and runs one grouped causal conv
over [x;B;C]. We keep separate projections and separate depthwise convs
for x, B, C so every weight has a clean logical axis for tensor-parallel
sharding ("ssm_inner" / "ssm_state"); expressiveness is unchanged.

Shapes: d_inner = expand * d_model; nheads = d_inner / head_dim;
x: [b, l, h, p]; B, C: [b, l, n] (ngroups = 1); dt: [b, l, h].

Scopes (``jax.named_scope``, op metadata only): ``mamba/proj`` (input
projections, dt, A), ``mamba/conv`` (the three causal convolutions),
``mamba/ssd`` (the state-space scan and its D skip), ``mamba/gate``
(gated RMSNorm and output projection); see docs/OBSERVABILITY.md.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from .layers import dense_init

Pytree = Any

D_CONV = 4           # depthwise conv width (Mamba2 default)
DEFAULT_CHUNK = 128


def init_mamba2(key, d_model: int, d_state: int, *, expand: int = 2,
                head_dim: int = 64, dtype=jnp.float32
                ) -> tuple[Pytree, Pytree]:
    d_inner = expand * d_model
    nheads = d_inner // head_dim
    ks = jax.random.split(key, 9)
    p = {
        "wz": dense_init(ks[0], (d_model, d_inner), dtype),
        "wx": dense_init(ks[1], (d_model, d_inner), dtype),
        "wB": dense_init(ks[2], (d_model, d_state), dtype),
        "wC": dense_init(ks[3], (d_model, d_state), dtype),
        "wdt": dense_init(ks[4], (d_model, nheads), dtype),
        "conv_x": dense_init(ks[5], (D_CONV, d_inner), dtype,
                             fan_in=D_CONV),
        "conv_B": dense_init(ks[6], (D_CONV, d_state), dtype, fan_in=D_CONV),
        "conv_C": dense_init(ks[7], (D_CONV, d_state), dtype, fan_in=D_CONV),
        "A_log": jnp.zeros((nheads,), jnp.float32),      # A = -exp(A_log)
        "D": jnp.ones((nheads,), jnp.float32),
        "dt_bias": jnp.zeros((nheads,), jnp.float32),
        "norm_scale": jnp.ones((d_inner,), dtype),
        "wo": dense_init(ks[8], (d_inner, d_model), dtype, fan_in=d_inner),
    }
    a = {
        "wz": ("embed", "ssm_inner"), "wx": ("embed", "ssm_inner"),
        "wB": ("embed", "ssm_state"), "wC": ("embed", "ssm_state"),
        "wdt": ("embed", "ssm_heads"),
        "conv_x": ("conv", "ssm_inner"), "conv_B": ("conv", "ssm_state"),
        "conv_C": ("conv", "ssm_state"),
        "A_log": ("ssm_heads",), "D": ("ssm_heads",),
        "dt_bias": ("ssm_heads",), "norm_scale": ("ssm_inner",),
        "wo": ("ssm_inner", "embed"),
    }
    return p, a


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray,
                 state: jnp.ndarray | None = None) -> jnp.ndarray:
    """Depthwise causal conv. x: [b, l, c]; w: [D_CONV, c].
    state: [b, D_CONV-1, c] trailing context (decode) or None (zeros)."""
    b, l, c = x.shape
    if state is None:
        state = jnp.zeros((b, D_CONV - 1, c), x.dtype)
    xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    out = sum(xp[:, i:i + l] * w[i][None, None, :] for i in range(D_CONV))
    return jax.nn.silu(out)


def _segsum_decay(da_cs: jnp.ndarray) -> jnp.ndarray:
    """Intra-chunk decay matrix L[q, k] = exp(sum_{j=k+1..q} dA_j) for
    q >= k else 0.  da_cs: [..., Q] inclusive cumsum of dA."""
    diff = da_cs[..., :, None] - da_cs[..., None, :]   # [..., Q, Q]
    q = da_cs.shape[-1]
    tri = jnp.tril(jnp.ones((q, q), bool))
    # Mask before exp: above the diagonal diff is positive and can
    # overflow, and the inf there would turn the backward pass's zero
    # cotangent into NaN.
    return jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)


def ssd_chunked(x: jnp.ndarray, dA: jnp.ndarray, B: jnp.ndarray,
                C: jnp.ndarray, chunk: int = DEFAULT_CHUNK,
                init_state: jnp.ndarray | None = None
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """SSD scan. x:[b,l,h,p] (pre-multiplied by dt), dA:[b,l,h] (= dt*A),
    B,C:[b,l,n]. Returns (y [b,l,h,p], final_state [b,h,n,p])."""
    b, l, h, p = x.shape
    n = B.shape[-1]
    q = min(chunk, l)
    nc = -(-l // q)
    pad = nc * q - l
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dA = jnp.pad(dA, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))

    xc = x.reshape(b, nc, q, h, p)
    dac = dA.astype(jnp.float32).reshape(b, nc, q, h)
    bc = B.reshape(b, nc, q, n)
    cc = C.reshape(b, nc, q, n)

    da_cs = jnp.cumsum(dac, axis=2)                   # [b,nc,q,h]
    # ---- intra-chunk (dual quadratic form) ----
    L = _segsum_decay(da_cs.transpose(0, 1, 3, 2))    # [b,nc,h,q,q]
    cb = jnp.einsum("bcqn,bckn->bcqk", cc.astype(jnp.float32),
                    bc.astype(jnp.float32))           # [b,nc,q,k]
    y_diag = jnp.einsum("bchqk,bcqk,bckhp->bcqhp",
                        L, cb, xc.astype(jnp.float32))

    # ---- chunk summary states ----
    decay_to_end = jnp.exp(da_cs[:, :, -1:, :] - da_cs)  # [b,nc,q,h]
    s_chunk = jnp.einsum("bcqn,bcqh,bcqhp->bchnp",
                         bc.astype(jnp.float32), decay_to_end,
                         xc.astype(jnp.float32))      # [b,nc,h,n,p]
    da_tot = da_cs[:, :, -1, :]                       # [b,nc,h]

    # ---- inter-chunk recurrence (scan over chunks) ----
    def body(s_run, inp):
        s_c, da_t = inp                               # [b,h,n,p], [b,h]
        s_out = s_run                                  # state BEFORE chunk
        s_next = s_run * jnp.exp(da_t)[..., None, None] + s_c
        return s_next, s_out

    s0 = (init_state.astype(jnp.float32) if init_state is not None
          else jnp.zeros((b, h, n, p), jnp.float32))
    s_final, s_before = jax.lax.scan(
        body, s0, (s_chunk.transpose(1, 0, 2, 3, 4),
                   da_tot.transpose(1, 0, 2)))
    s_before = s_before.transpose(1, 0, 2, 3, 4)      # [b,nc,h,n,p]

    y_off = jnp.einsum("bcqn,bchnp,bcqh->bcqhp",
                       cc.astype(jnp.float32), s_before, jnp.exp(da_cs))
    y = (y_diag + y_off).reshape(b, nc * q, h, p)[:, :l]
    return y.astype(x.dtype), s_final


def apply_mamba2(params: Pytree, x: jnp.ndarray, *, head_dim: int = 64,
                 chunk: int = DEFAULT_CHUNK, cache: Pytree | None = None
                 ) -> tuple[jnp.ndarray, Pytree | None]:
    """x: [b, l, d_model]. cache (decode): {"conv_x","conv_B","conv_C":
    [b, D_CONV-1, *], "ssm": [b, h, n, p]}. Returns (y, new_cache|None)."""
    b, l, d = x.shape
    d_inner = params["wx"].shape[1]
    h = d_inner // head_dim
    n = params["wB"].shape[1]

    with jax.named_scope("mamba/proj"):
        z = x @ params["wz"]                           # [b,l,di]
        xin = x @ params["wx"]
        Braw = x @ params["wB"]
        Craw = x @ params["wC"]
        dt = jax.nn.softplus(x.astype(jnp.float32) @
                             params["wdt"].astype(jnp.float32)
                             + params["dt_bias"])      # [b,l,h]
        A = -jnp.exp(params["A_log"])                  # [h]

    decode = cache is not None and l == 1
    cstate = cache if cache is not None else {}
    with jax.named_scope("mamba/conv"):
        xc = _causal_conv(xin, params["conv_x"], cstate.get("conv_x"))
        Bc = _causal_conv(Braw, params["conv_B"], cstate.get("conv_B"))
        Cc = _causal_conv(Craw, params["conv_C"], cstate.get("conv_C"))

    with jax.named_scope("mamba/ssd"):
        xh = xc.reshape(b, l, h, head_dim)
        x_dt = xh.astype(jnp.float32) * dt[..., None]
        dA = dt * A[None, None, :]

        if decode:
            s = cstate["ssm"].astype(jnp.float32)      # [b,h,n,p]
            da1 = jnp.exp(dA[:, 0])                    # [b,h]
            s_new = s * da1[..., None, None] + jnp.einsum(
                "bn,bhp->bhnp", Bc[:, 0].astype(jnp.float32), x_dt[:, 0])
            y = jnp.einsum("bn,bhnp->bhp", Cc[:, 0].astype(jnp.float32),
                           s_new)
            y = y[:, None]                             # [b,1,h,p]
            new_cache = {
                "conv_x": jnp.concatenate([cstate["conv_x"][:, 1:], xin],
                                          axis=1),
                "conv_B": jnp.concatenate([cstate["conv_B"][:, 1:], Braw],
                                          axis=1),
                "conv_C": jnp.concatenate([cstate["conv_C"][:, 1:], Craw],
                                          axis=1),
                "ssm": s_new.astype(cstate["ssm"].dtype),
            }
        else:
            y, s_final = ssd_chunked(x_dt, dA, Bc, Cc, chunk=chunk,
                                     init_state=cstate.get("ssm"))
            new_cache = None
            if cache is not None:   # chunked prefill into state
                new_cache = {
                    "conv_x": jnp.concatenate([cstate["conv_x"], xin],
                                              axis=1)[:, -(D_CONV - 1):],
                    "conv_B": jnp.concatenate([cstate["conv_B"], Braw],
                                              axis=1)[:, -(D_CONV - 1):],
                    "conv_C": jnp.concatenate([cstate["conv_C"], Craw],
                                              axis=1)[:, -(D_CONV - 1):],
                    "ssm": s_final.astype(cstate["ssm"].dtype),
                }

        y = y + params["D"][None, None, :, None] * xh.astype(jnp.float32)
        y = y.reshape(b, l, d_inner)

    with jax.named_scope("mamba/gate"):
        # gated RMSNorm (Mamba2): norm(y * silu(z))
        g = y * jax.nn.silu(z.astype(jnp.float32))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                              + 1e-6)
        g = g * params["norm_scale"].astype(jnp.float32)
        out = g.astype(x.dtype) @ params["wo"]
    return out, new_cache


def init_mamba2_cache(batch: int, d_model: int, d_state: int, *,
                      expand: int = 2, head_dim: int = 64,
                      dtype=jnp.float32) -> Pytree:
    d_inner = expand * d_model
    h = d_inner // head_dim
    return {
        "conv_x": jnp.zeros((batch, D_CONV - 1, d_inner), dtype),
        "conv_B": jnp.zeros((batch, D_CONV - 1, d_state), dtype),
        "conv_C": jnp.zeros((batch, D_CONV - 1, d_state), dtype),
        "ssm": jnp.zeros((batch, h, d_state, head_dim), dtype),
    }
