"""In-graph round telemetry: the :class:`Telemetry` pytree + its builders.

Everything here is jit-compatible and runs INSIDE the compiled round step
when it is built with ``with_telemetry=True``; the flag defaults to off
and the off path emits the exact graph it did before (bit-identical — the
parity tests pin it). Fields a path does not produce stay ``None``, which
is an empty pytree subtree, so one NamedTuple serves the synchronous,
asynchronous, and pooled steps without shape games.

Metric definitions (see ``docs/OBSERVABILITY.md`` for the full math):

  consensus_dist  (1/m) sum_i ||x^{t+1}(i) - xbar||^2 — Lemma 4's LHS.
  local_drift     the same functional over the published z^t.
  live_edges      realized nonzero off-diagonal entries of the round's
                  effective mixing matrix — the directed edges that
                  actually carried a message.
  wire_bits       message_bits(d, quant) * live_edges — the REALIZED wire
                  bill, to cross-check against ``CommLedger``'s
                  expectation-based accounting (equal for deterministic
                  schedules, a realized-vs-expected residual for sampled
                  ones).
  quant_err_sq    mean_i ||Q(delta_i) - delta_i||^2 over participating
                  clients, replaying the codec's exact draws — in the
                  round steps, over a :data:`QUANT_SAMPLE_LANES` strided
                  lane sample (sampled profiling; each sampled lane is
                  still an exact replay).
  quant_bound     the paper's Assumption-4 budget mean_i sum_l d_l/4 *
                  s_{l,i}^2 next to it (eq7 and lemma5 quantize the same
                  delta, so one observed-vs-bound pair covers both).
  quant_sat_frac  fraction of codes pinned at qmin/qmax. Per-tensor
                  scaling places each (client, leaf) amax exactly at a
                  rail, so a floor of ~n_leaves*m/total is expected;
                  growth beyond that means fixed-s clipping is biting.
  staleness_hist  [max_staleness + 2] counts of per-client version lag;
                  the last bucket collects lags past the hard cutoff.
  dropped_edges   base-support edges hard-zeroed by the staleness cutoff
                  (live_edges + dropped_edges == the base matrix's ready
                  live count — the invariant the async tests pin).
  cohort_size     pooled: resident lanes this round/event.
  placement_boundary_lanes
                  sparse backend: wire lane slots of the run's block
                  realization — the compile-time boundary cut the
                  placement pass minimizes, constant per run, surfaced
                  so placed runs are auditable next to wire_bits.

The quantizer replay draws its stochastic-rounding keys through
``core.mixing._quant_leaf_keys`` — the same single source of truth the
dense/sparse/pooled mixers use — so on the dense reference backend the
replayed codes are the codes the round actually applied. The planar-wire
backend draws its uniforms at the padded planar shape, so its elementwise
draws differ; scales (shared ``scale_from_amax``) and therefore the bound
are identical, and the observed MSE is statistically the wire's.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.mixing import _quant_leaf_keys
from ..core.quantize import QuantConfig, dequantize_int, message_bits, \
    quantize_int

Pytree = Any

__all__ = ["QUANT_SAMPLE_LANES", "Telemetry", "client_dim",
           "live_edge_count", "wire_bits_for", "quant_round_telemetry",
           "staleness_histogram", "dropped_edge_count", "telemetry_host"]

# Lane-sample size the round steps pass to ``quant_round_telemetry``.
# The replay is one extra codec pass over the wire deltas — per-element
# work (threefry draws + quantize arithmetic) that rivals the mixer's own
# codec — so replaying every lane every round would roughly double the
# codec share of the round and blow the <= 1.10x telemetry-overhead gate.
# A strided sample keeps each sampled lane an EXACT wire replay (same
# per-(leaf, client) keys) and caps the cost at ~sample/m of the full
# pass; pass ``sample_lanes=None`` for the full-population replay (the
# parity tests do). Two lanes hold the marginal cost near 3-4% of a
# training-shaped round — enough margin that runner noise cannot push
# the gated ratio over 1.10x.
QUANT_SAMPLE_LANES = 2


class Telemetry(NamedTuple):
    """Per-round in-graph telemetry. ``None`` = not produced by this
    execution path (an empty pytree subtree — jit/scan/donation safe)."""

    consensus_dist: jnp.ndarray | None = None
    local_drift: jnp.ndarray | None = None
    live_edges: jnp.ndarray | None = None
    wire_bits: jnp.ndarray | None = None
    quant_err_sq: jnp.ndarray | None = None
    quant_bound: jnp.ndarray | None = None
    quant_sat_frac: jnp.ndarray | None = None
    staleness_hist: jnp.ndarray | None = None
    dropped_edges: jnp.ndarray | None = None
    cohort_size: jnp.ndarray | None = None
    placement_boundary_lanes: jnp.ndarray | None = None


def client_dim(stacked: Pytree) -> int:
    """d — parameters per client of a client-stacked pytree (static)."""
    return int(sum(int(np.prod(l.shape[1:]))
                   for l in jax.tree.leaves(stacked)))


def live_edge_count(W, valid=None) -> jnp.ndarray:
    """Nonzero off-diagonal entries of the (possibly traced) effective
    mixing matrix — the round's realized directed message edges. The
    schedules already encode participation in ``W_t`` (inactive rows are
    ``e_i``, inactive columns 0), so no extra mask is needed; ``valid``
    [k] restricts to real lanes for capacity-padded pooled matrices."""
    Wj = jnp.asarray(W, jnp.float32)
    k = Wj.shape[0]
    off = Wj * (1.0 - jnp.eye(k, dtype=jnp.float32))
    if valid is not None:
        off = off * valid[:, None] * valid[None, :]
    return jnp.sum((off != 0.0).astype(jnp.float32))


def wire_bits_for(d: int, quant: QuantConfig | None,
                  live_edges, model_parallel: int = 1) -> jnp.ndarray:
    """Realized wire bits: one ``message_bits`` payload per live directed
    edge — the same per-edge convention every ``comm_cost`` bill uses, so
    telemetry and ledger are directly comparable.

    ``model_parallel`` > 1 reports the PER-DEVICE-COLUMN bill of the 2D
    ``(clients, model)`` mesh instead: each column's boundary ppermutes
    carry only its ``1/model_parallel`` slice of every payload, so the
    column bill is the total divided by the degree (the sum over columns
    recovers the 1D number — the same convention as
    ``comm_cost.plan_round_bits(model_parallel=...)``, which the 2D mesh
    tests cross-check against this function)."""
    if model_parallel < 1:
        raise ValueError(f"model_parallel={model_parallel} must be >= 1")
    qc = quant if quant is not None else QuantConfig(bits=32)
    return (jnp.float32(message_bits(d, qc))
            * jnp.asarray(live_edges, jnp.float32)
            / jnp.float32(model_parallel))


def quant_round_telemetry(x: Pytree, z_eff: Pytree, quant: QuantConfig,
                          key_q, leaf_keys: jax.Array | None = None,
                          lane_weight: jax.Array | None = None,
                          sample_lanes: int | None = None):
    """Replay the round's quantization and measure its error.

    ``x`` / ``z_eff`` are the client-stacked held state and effective
    published state (inactive lanes already gated to x, so their delta is
    exactly 0 — they quantize to Q(0) and contribute nothing, same as the
    mixers). Per client i the codec quantizes ``delta_i = z_eff_i - x_i``
    leaf by leaf; this replays ``quantize_int`` under the shared
    ``_quant_leaf_keys`` discipline (pass the pooled path's gathered
    ``leaf_keys`` [n_leaves, k, 2] to replay a cohort) and returns

      err_sq   mean_i ||Q(delta_i) - delta_i||^2      (observed)
      bound    mean_i sum_l d_l / 4 * s_{l,i}^2       (Assumption 4)
      sat_frac fraction of codes at qmin/qmax          (amax saturation)

    ``lane_weight`` [m] averages err/bound over a subset of lanes (the
    async path passes the ready mask so busy clients' zero deltas don't
    dilute the observed error). ``sample_lanes`` restricts the replay to
    a strided sample of that many client lanes (sampled profiling — see
    :data:`QUANT_SAMPLE_LANES`): each sampled lane still replays its
    exact wire draws, the means are just taken over the sample.
    """
    leaves_x = jax.tree.leaves(x)
    leaves_z = jax.tree.leaves(z_eff)
    n_leaves = len(leaves_x)
    m = leaves_x[0].shape[0]
    if leaf_keys is None and quant.stochastic:
        leaf_keys = _quant_leaf_keys(key_q, n_leaves, m)
    ids = None
    if sample_lanes is not None and sample_lanes < m:
        ids = np.arange(0, m, max(1, m // sample_lanes))[:sample_lanes]
        if lane_weight is not None:
            lane_weight = jnp.asarray(lane_weight)[ids]
    m_eff = m if ids is None else len(ids)

    err = jnp.zeros((m_eff,), jnp.float32)
    bound = jnp.zeros((m_eff,), jnp.float32)
    sat = jnp.zeros((m_eff,), jnp.float32)
    d_total = 0
    for li, (xl, zl) in enumerate(zip(leaves_x, leaves_z)):
        delta = (zl - xl).astype(jnp.float32).reshape(m, -1)
        d_l = delta.shape[1]
        d_total += d_l
        keys_l = leaf_keys[li] if quant.stochastic else None
        if ids is not None:
            delta = delta[ids]
            keys_l = None if keys_l is None else keys_l[ids]

        def one(drow, k):
            code, s = quantize_int(drow, quant, k)
            e = jnp.sum((dequantize_int(code, s) - drow) ** 2)
            nsat = jnp.sum(((code == quant.qmin) | (code == quant.qmax))
                           .astype(jnp.float32))
            return e, s, nsat

        if quant.stochastic:
            e_l, s_l, sat_l = jax.vmap(one)(delta, keys_l)
        else:
            e_l, s_l, sat_l = jax.vmap(lambda d: one(d, None))(delta)
        err = err + e_l
        bound = bound + jnp.float32(d_l / 4.0) * s_l * s_l
        sat = sat + sat_l

    if lane_weight is not None:
        w = jnp.asarray(lane_weight, jnp.float32)
        denom = jnp.maximum(w.sum(), 1.0)
        return (jnp.sum(err * w) / denom, jnp.sum(bound * w) / denom,
                jnp.sum(sat * w) / (denom * jnp.float32(d_total)))
    return (jnp.mean(err), jnp.mean(bound),
            jnp.mean(sat) / jnp.float32(d_total))


def staleness_histogram(version: jax.Array, max_staleness: int
                        ) -> jnp.ndarray:
    """[max_staleness + 2] int32 counts of per-client version lag
    ``max_j version[j] - version[i]`` — buckets 0..max_staleness, plus a
    final overflow bucket for clients already past the hard cutoff
    (whose outgoing freshness is zeroed by ``staleness_weights``)."""
    lag = jnp.max(version) - version
    lagc = jnp.clip(lag, 0, max_staleness + 1)
    return jnp.zeros((max_staleness + 2,), jnp.int32).at[lagc].add(1)


def dropped_edge_count(W_base, version, ready,
                       max_staleness: int) -> jnp.ndarray:
    """Base-support directed edges the staleness HARD CUTOFF zeroed this
    event: ready row i, base weight on j nonzero, pairwise lag
    ``version[i] - version[j] > max_staleness``. Both supported discounts
    are strictly positive at or below the cutoff, so
    ``live_edges(W_eff) + dropped == live_edges(W_base restricted to
    ready rows)`` — the conservation the async telemetry tests pin."""
    Wj = jnp.asarray(W_base, jnp.float32)
    k = Wj.shape[0]
    s = jnp.maximum(version[:, None] - version[None, :], 0)
    off = (Wj * (1.0 - jnp.eye(k, dtype=jnp.float32))) != 0.0
    ready_row = jnp.asarray(ready, jnp.float32)[:, None] > 0
    return jnp.sum((off & ready_row & (s > max_staleness))
                   .astype(jnp.float32))


def telemetry_host(tel: Telemetry) -> dict:
    """One device transfer -> plain python values keyed by field name
    (``staleness_hist`` becomes a list of ints), ready for
    ``RunLog.round(**fields)``. ``None`` fields are omitted."""
    present = {k: v for k, v in tel._asdict().items() if v is not None}
    host = jax.device_get(present)
    out = {}
    for k, v in host.items():
        if k == "staleness_hist":
            out[k] = [int(c) for c in np.asarray(v)]
        else:
            out[k] = float(v)
    return out
