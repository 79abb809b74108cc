"""Mamba2 (arXiv:2405.21060) in plain float32: pre-norm residual blocks
of RMSNorm and the Mamba2 mixer, with an output head tied to the input
embedding.

Mixer: projections to z, x, B, C (one group) and dt; a depthwise causal
convolution of width 4 with SiLU on x, B and C; dt = softplus(dt +
dt_bias); A = -exp(A_log); the selective state-space recurrence
``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t``, ``y_t = C_t h_t + D x_t``,
evaluated here in its quadratic (attention-like) form over the whole
sequence; then the gated RMSNorm ``norm(y * silu(z))`` and the output
projection. The published model fuses z, x, B, C and dt into one input
projection and convolves x, B, C as one grouped tensor; splitting the
weight by rows computes the same function, and ``init`` stores it split
as the training program does.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .ops import Matmul, next_token_nll, normal, rms_norm

D_CONV = 4


def init(key, cfg: dict) -> dict:
    d, N, L = cfg["d_model"], cfg["ssm_state"], cfg["n_layers"]
    di = cfg["ssm_expand"] * d
    H = di // cfg["ssm_head_dim"]
    V = cfg["vocab_size"]
    dt = jnp.dtype(cfg["dtype"])
    k = jax.random.split(key, 12)
    f32 = jnp.float32
    # Published initialisation of the state-space scalars: A in [1, 16],
    # dt in [1e-3, 1e-1] log-uniform (dt_bias = softplus^-1(dt)), D = 1.
    a0 = jax.random.uniform(k[10], (L, H), f32, 1.0, 16.0)
    dt0 = jnp.exp(jax.random.uniform(k[11], (L, H), f32,
                                     jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "embed": {"table": normal(k[0], (V, d), d, dt)},
        "stages": [{
            "ln": {"scale": jnp.ones((L, d), dt)},
            "mixer": {
                "wz": normal(k[1], (L, d, di), d, dt),
                "wx": normal(k[2], (L, d, di), d, dt),
                "wB": normal(k[3], (L, d, N), d, dt),
                "wC": normal(k[4], (L, d, N), d, dt),
                "wdt": normal(k[5], (L, d, H), d, dt),
                "conv_x": normal(k[6], (L, D_CONV, di), D_CONV, dt),
                "conv_B": normal(k[7], (L, D_CONV, N), D_CONV, dt),
                "conv_C": normal(k[8], (L, D_CONV, N), D_CONV, dt),
                "A_log": jnp.log(a0),
                "D": jnp.ones((L, H), f32),
                "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
                "norm_scale": jnp.ones((L, di), dt),
                "wo": normal(k[9], (L, di, d), di, dt),
            },
        }],
        "final_norm": {"scale": jnp.ones((d,), dt)},
    }


def _conv(x, w):
    """Depthwise causal convolution, then SiLU. x [l, c]; w [4, c]."""
    l = x.shape[0]
    xp = jnp.concatenate([jnp.zeros((D_CONV - 1, x.shape[1]), x.dtype), x])
    return jax.nn.silu(sum(xp[i:i + l] * w[i] for i in range(D_CONV)))


def _ssm(mm: Matmul, x, dt, A, B, C):
    """y[t] = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s.
    x [l, H, P]; dt [l, H]; A [H]; B, C [l, N]."""
    l = x.shape[0]
    cs = jnp.cumsum(dt * A[None, :], axis=0)                 # [l, H]
    seg = cs[:, None, :] - cs[None, :, :]                     # [t, s, H]
    causal = jnp.tril(jnp.ones((l, l), bool))[:, :, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    cb = mm("tn,sn->ts", C, B)
    w = cb[:, :, None] * decay * dt[None, :, :]               # [t, s, H]
    return mm("tsh,shp->thp", w, x)


def loss(params: dict, tokens, targets, cfg: dict, mm: Matmul):
    """Mean next-token loss of one sequence; params in float32."""
    table = params["embed"]["table"]
    st = params["stages"][0]
    x = table[tokens]
    l = tokens.shape[0]
    P = cfg["ssm_head_dim"]
    for i in range(cfg["n_layers"]):
        p = jax.tree.map(lambda a: a[i], st["mixer"])
        h = rms_norm(x, st["ln"]["scale"][i])
        z = mm("ld,de->le", h, p["wz"])
        xi = _conv(mm("ld,de->le", h, p["wx"]), p["conv_x"])
        B = _conv(mm("ld,dn->ln", h, p["wB"]), p["conv_B"])
        C = _conv(mm("ld,dn->ln", h, p["wC"]), p["conv_C"])
        dt = jax.nn.softplus(mm("ld,dh->lh", h, p["wdt"]) + p["dt_bias"])
        A = -jnp.exp(p["A_log"])
        xh = xi.reshape(l, -1, P)
        y = _ssm(mm, xh, dt, A, B, C) + p["D"][None, :, None] * xh
        g = y.reshape(l, -1) * jax.nn.silu(z)
        x = x + mm("le,ed->ld", rms_norm(g, p["norm_scale"]), p["wo"])
    h = rms_norm(x, params["final_norm"]["scale"])
    return next_token_nll(mm, h, table, targets)
