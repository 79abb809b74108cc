"""Work a cell must do, computed from its configuration's shapes alone.

``train_flops_per_token``: the floating-point operations that one
training token needs in the forward and backward passes (3x the
forward: one pass forward, two backward), not counting anything the
program recomputes. Forward per token:

* 2 per matmul parameter (the tied output head counts once, as the
  head; the embedding lookup is no matmul);
* attention, causal: scores and values, ``2 * 2 * heads * head_dim``
  per visible position, on average ``(seq + 1) / 2`` of them;
* Mamba2: the depthwise convolution (2 per tap and channel) and the
  chunked state-space scan at chunk ``Q``: within a chunk ``C B^T``
  (``2 N`` per visible position, ``(Q + 1) / 2`` on average, shared by
  the heads) and the decay-weighted sum (``2 P`` per head and visible
  position), and across chunks the chunk state and its read-out
  (``2 * 2 * heads * P * N``).

``codec_least_bytes``: the least HBM traffic of one client's b-bit
gossip codec in one round (see the function).
"""
from __future__ import annotations

SSD_CHUNK = 128
CONV_WIDTH = 4


def matmul_params(cfg: dict) -> int:
    """Parameters that multiply activations in a matmul."""
    d, L, V = cfg["d_model"], cfg["n_layers"], cfg["vocab_size"]
    if cfg.get("ssm_state"):
        di = cfg["ssm_expand"] * d
        N = cfg["ssm_state"]
        H = di // cfg["ssm_head_dim"]
        per_layer = d * (2 * di + 2 * N + H) + di * d
    else:
        H, hd, f = cfg["n_heads"], cfg["head_dim"], cfg["d_ff"]
        per_layer = 4 * d * H * hd + 3 * d * f
    return L * per_layer + V * d


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    d, L = cfg["d_model"], cfg["n_layers"]
    flops = 2.0 * matmul_params(cfg)
    if cfg.get("ssm_state"):
        di = cfg["ssm_expand"] * d
        N = cfg["ssm_state"]
        P = cfg["ssm_head_dim"]
        H = di // P
        Q = min(SSD_CHUNK, seq)
        vis = (Q + 1) / 2
        conv = 2 * CONV_WIDTH * (di + 2 * N)
        ssd = 2 * N * vis + 2 * H * P * vis + 2 * 2 * H * P * N
        flops += L * (conv + ssd)
    else:
        H, hd = cfg["n_heads"], cfg["head_dim"]
        flops += L * 2 * 2 * H * hd * (seq + 1) / 2
    return flops


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 3.0 * forward_flops_per_token(cfg, seq)


def leaf_sizes(cfg: dict) -> list:
    """Element counts of one client's parameter leaves, in the order the
    wire lays them out (sorted names, as a pytree flattens dicts)."""
    d, L, V = cfg["d_model"], cfg["n_layers"], cfg["vocab_size"]
    if cfg.get("ssm_state"):
        di = cfg["ssm_expand"] * d
        N = cfg["ssm_state"]
        H = di // cfg["ssm_head_dim"]
        mixer = {"A_log": H, "D": H, "conv_B": CONV_WIDTH * N,
                 "conv_C": CONV_WIDTH * N, "conv_x": CONV_WIDTH * di,
                 "dt_bias": H, "norm_scale": di, "wB": d * N, "wC": d * N,
                 "wdt": d * H, "wo": di * d, "wx": d * di, "wz": d * di}
        return ([V * d, d, L * d]
                + [L * mixer[k] for k in sorted(mixer)])
    H, hd, f = cfg["n_heads"], cfg["head_dim"], cfg["d_ff"]
    attn = [L * d * H * hd] * 4
    mlp = [L * f * d] * 3
    return [V * d] + attn + mlp


def codec_least_bytes(cfg: dict, bits: int, streams: int) -> float:
    """Least bytes one client's codec moves in a round, at ``bits`` per
    value, mixing ``streams`` quantized messages (its own and each
    neighbour's):

    * encode reads the float32 delta once (4 bytes a value) and writes
      the packed words (``bits / 8`` a value) and one float32 scale per
      leaf;
    * decode reads each stream's words and scales, and reads and writes
      the client's parameters once, in their stored dtype.

    Random rounding noise is not counted: a codec may draw it in
    registers."""
    sizes = leaf_sizes(cfg)
    n, nl = sum(sizes), len(sizes)
    pbytes = 2 if cfg["dtype"] == "bfloat16" else 4
    if cfg.get("ssm_state"):
        # A_log, D and dt_bias are stored in float32.
        di = cfg["ssm_expand"] * cfg["d_model"]
        n32 = 3 * cfg["n_layers"] * (di // cfg["ssm_head_dim"])
        param_bytes = pbytes * (n - n32) + 4 * n32
    else:
        param_bytes = pbytes * n
    packed = n * bits / 8 + 4 * nl
    encode = 4 * n + packed
    decode = streams * packed + 2 * param_bytes
    return float(encode + decode)
