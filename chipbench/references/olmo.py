"""OLMo (arXiv:2402.00838) in plain float32: pre-norm decoder blocks
with non-parametric LayerNorm, full multi-head causal attention with
rotary embeddings (rotate-half), SwiGLU MLP, and an output
head tied to the input embedding.

``init`` lays the weights out as the training program stores them
(names, stacked layer axis, dtype), so the benchmark can hand the same
weights to the program and to this reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .ops import Matmul, layer_norm, next_token_nll, normal

def init(key, cfg: dict) -> dict:
    d, H, hd = cfg["d_model"], cfg["n_heads"], cfg["head_dim"]
    f, V, L = cfg["d_ff"], cfg["vocab_size"], cfg["n_layers"]
    dt = jnp.dtype(cfg["dtype"])
    k = jax.random.split(key, 8)
    return {
        "embed": {"table": normal(k[0], (V, d), d, dt)},
        "stages": [{
            "ln1": {},
            "attn": {"wq": normal(k[1], (L, d, H, hd), d, dt),
                     "wk": normal(k[2], (L, d, H, hd), d, dt),
                     "wv": normal(k[3], (L, d, H, hd), d, dt),
                     "wo": normal(k[4], (L, H, hd, d), H * hd, dt)},
            "ln2": {},
            "mlp": {"wg": normal(k[5], (L, d, f), d, dt),
                    "wu": normal(k[6], (L, d, f), d, dt),
                    "wd": normal(k[7], (L, f, d), f, dt)},
        }],
        "final_norm": {},
    }


def _rope(x, theta: float):
    """x [l, H, hd]: rotate the two halves of each head by position."""
    l, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def loss(params: dict, tokens, targets, cfg: dict, mm: Matmul):
    """Mean next-token loss of one sequence; params in float32."""
    table = params["embed"]["table"]
    st = params["stages"][0]
    x = table[tokens]
    l = tokens.shape[0]
    causal = jnp.tril(jnp.ones((l, l), bool))
    hd = cfg["head_dim"]
    for i in range(cfg["n_layers"]):
        a = st["attn"]
        h = layer_norm(x)
        q = _rope(mm("ld,dhk->lhk", h, a["wq"][i]), cfg["rope_theta"])
        k = _rope(mm("ld,dhk->lhk", h, a["wk"][i]), cfg["rope_theta"])
        v = mm("ld,dhk->lhk", h, a["wv"][i])
        s = mm("qhk,shk->hqs", q, k) / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = mm("hqs,shk->qhk", p, v)
        x = x + mm("qhk,hkd->qd", o, a["wo"][i])
        w = st["mlp"]
        h = layer_norm(x)
        u = jax.nn.silu(mm("ld,df->lf", h, w["wg"][i])) \
            * mm("ld,df->lf", h, w["wu"][i])
        x = x + mm("lf,fd->ld", u, w["wd"][i])
    return next_token_nll(mm, layer_norm(x), table, targets)
