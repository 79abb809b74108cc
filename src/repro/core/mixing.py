"""Gossip mixing x^{t+1}(i) = sum_l w_{i,l} z^t(l)  (paper eqs. 5 and 7).

Client copies are stored *stacked*: every param leaf carries a leading
``client`` axis of size ``m``. All topologies — static ``MixingSpec`` ring
/ torus / arbitrary graphs AND time-varying ``TopologySchedule`` events —
lower through one plan/compile/execute pipeline:

  compile:  topology -> :class:`~repro.core.gossip_plan.GossipPlan` — a
            program of permutation steps covering every support edge
            exactly once, plus self weights (static) or a per-round
            weight gather from the sampled ``W_t`` (schedules).

  execute:  one of two backends consumes the plan:

  * ``dense``  — ``x' = W @ Z`` as an einsum over the client axis. Under
    pjit with the client axis sharded, XLA lowers this to an m-way
    all-gather. Works for ANY mixing matrix; this is the reference.

  * ``sparse`` — a ``shard_map`` that realizes the plan as *masked*
    ``ppermute`` steps: O(degree) neighbor traffic per round regardless
    of how ``W_t`` was sampled. Edges a round did not sample get weight
    0 — the wire schedule is static (compile once), the mask is the
    round's realized topology. Each shard holds a CONTIGUOUS BLOCK of
    ``m_local = m / n_shards`` clients (``m_local == 1`` is the classic
    one-client-per-shard layout); with ``m_local > 1`` the compiled
    :class:`~repro.core.gossip_plan.BlockPlan` turns intra-block edges
    into on-device lane gathers (zero wire) and ships only the
    boundary lanes through shard-level ppermutes, so ``m`` scales past
    the device count at O(n_shards * boundary_degree) wire bytes.

``ring`` and ``torus`` impls are thin plan instances of the sparse
backend (their shift decompositions map 1:1 onto ICI links).

The sparse backend's hot loop runs on a FLAT WIRE BUFFER
(:mod:`repro.core.wire_layout`): the model pytree is flattened once per
round into a single lane-aligned planar array, so quantize/pack, each
plan step's ``ppermute``, and the fused dequantize/mix run once per round
on one contiguous buffer instead of once per leaf per step. Quantized
variants (Algorithm 2) transmit the *packed uint32 wire words* of
``Q(z - x)`` with the per-leaf f32 scales bitcast into the stream tail —
ONE collective launch per plan step, and the compiled HLO actually moves
b/32 of the bytes. The codec itself has two interchangeable backends
behind ``MixerConfig.wire``: ``planar`` (the Pallas
``kernels.quantize_pack`` / ``kernels.dequant_mix`` buffer kernels,
auto-selected on TPU) and ``seq`` (a pure-XLA lowering of the identical
math — the CPU default and the kernels' parity oracle: bit-identical
wire words/scales, few-ulp fused output).

2D ``(clients, model)`` MESHES (``make_client_mesh(model_parallel=...)``
plus model-sharded ``param_specs`` from ``sharding.rules``) compose with
the sparse backend transparently: each device holds only its model slice
of its client block, the wire buffer is the per-shard layout, and the
boundary ppermutes — still along the CLIENT axes only — ship just that
slice, so per-device wire drops ~linearly with model parallelism.
Quantizer scales stay bitwise-consistent across model shards via a
``pmax`` amax all-reduce, and stochastic rounding replays the 1D PRNG
stream sliced per shard (see :func:`_make_sparse_exec`).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .gossip_plan import GossipPlan
from .quantize import QuantConfig, dequantize_int, quantize_int
from .topology import MixingSpec, TopologySchedule
from .wire_layout import WireLayout, rows_to_planar

Pytree = Any

__all__ = ["MixerConfig", "make_mixer", "make_scheduled_mixer", "mix_dense",
           "make_plan_mixer", "make_event_mixer", "make_fused_tail",
           "execute_plan_reference", "consensus_distance"]

_IMPLS = ("auto", "dense", "ring", "torus", "sparse")
_WIRES = ("auto", "seq", "planar")


def _clients_per_shard(mesh, client_axes: Sequence[str], m: int) -> int | None:
    """The sparse backend maps a CONTIGUOUS BLOCK of ``m_local`` clients
    onto each mesh shard (``m = n_shards * m_local`` — the layout jax's
    leading-axis sharding produces). Returns ``m_local`` when ``mesh``'s
    client axes multiply out to a divisor of ``m`` (1 = the classic
    one-client-per-shard layout), else None (mesh unusable)."""
    if mesh is None or not client_axes:
        return None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if any(a not in sizes for a in client_axes):
        return None
    n_shards = int(np.prod([sizes[a] for a in client_axes]))
    if n_shards < 1 or m % n_shards:
        return None
    return m // n_shards


@dataclasses.dataclass(frozen=True)
class MixerConfig:
    """Gossip mixer selection.

    impl:  "auto" | "dense" | "ring" | "torus" | "sparse".
           "dense" is the einsum reference (any W, all-gather traffic);
           "sparse" executes the compiled GossipPlan as masked ppermutes
           (any bounded-degree topology, incl. time-varying schedules;
           needs a mesh whose client axes multiply to a divisor of m —
           each shard carries a contiguous block of m_local clients,
           m_local == 1 being the classic one-client-per-shard layout);
           "ring"/"torus" are the plan instances for those static specs;
           "auto" picks a sparse realization when the mesh fits (except
           for complete graphs, where the all-gather is optimal), else
           "dense".
    quant: None disables Algorithm 2; a QuantConfig moves packed uint32
           wire words through the collectives.
    wire:  quantized-sparse wire codec backend. Both run the same flat
           wire-buffer path (one planar buffer per round, scales in the
           stream tail, one ppermute per plan step) and produce
           numerically identical results: "planar" executes the Pallas
           buffer kernels (quantize_pack_buffer / dequant_mix_buffer,
           interpret mode off-TPU), "seq" the pure-XLA lowering of the
           same math, "auto" picks planar on TPU and seq elsewhere.
    """

    impl: str = "auto"
    quant: QuantConfig | None = None
    wire: str = "auto"

    def __post_init__(self):
        if self.impl not in _IMPLS:
            raise ValueError(
                f"unknown mixer impl {self.impl!r}; allowed impls: "
                + " | ".join(repr(i) for i in _IMPLS))
        if self.wire not in _WIRES:
            raise ValueError(
                f"unknown wire codec {self.wire!r}; allowed: "
                + " | ".join(repr(w) for w in _WIRES))

    def resolved_impl(self, spec, mesh,
                      client_axes: Sequence[str] = ("clients",)) -> str:
        if self.impl != "auto":
            return self.impl
        # Any mesh whose shard count divides m fits: each shard carries a
        # block of m_local clients (m_local == 1 is the classic layout).
        # A mesh with matching client axes is treated as deliberate
        # opt-in — make_client_mesh only ever builds exact-fit meshes, so
        # auto cannot trip this on a mesh built for something else; and
        # even at large m_local the block realization moves only boundary
        # lanes where dense all-gathers the whole O(m) stacked axis.
        if _clients_per_shard(mesh, client_axes, spec.m) is not None:
            if isinstance(spec, TopologySchedule):
                return "sparse"
            if spec.kind in ("ring", "torus"):
                return spec.kind
            # Arbitrary static graphs lower sparsely too (matchings) —
            # except a complete graph, where the all-gather IS optimal.
            if int(spec.graph.degrees().max()) < spec.m - 1:
                return "sparse"
        return "dense"


def _take_lanes(rows: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``rows[idx]`` over the lane axis for a short traced index vector of
    in-bounds lanes, as one dynamic slice per lane. XLA's TPU backend
    splits a gather of wide rows into 32768-element pieces, so a gather
    over a whole model's wire row grows the program with the model until
    compilation stalls."""
    return jnp.concatenate(
        [jax.lax.dynamic_slice_in_dim(rows, idx[j], 1, axis=0)
         for j in range(idx.shape[0])], axis=0)


def _put_lanes(out: jnp.ndarray, idx: jnp.ndarray,
               vals: jnp.ndarray) -> jnp.ndarray:
    """``out.at[idx].set(vals, mode="drop")`` over the lane axis as one
    dynamic update slice per lane (an index ``>= out.shape[0]`` writes
    the last lane's own row back) — the scatter counterpart of
    :func:`_take_lanes`. Each write touches one lane's row, not all
    ``m_local`` of them."""
    last = out.shape[0] - 1
    for j in range(idx.shape[0]):
        at = jnp.minimum(idx[j], last)
        row = jnp.where(idx[j] <= last, vals[j:j + 1],
                        jax.lax.dynamic_slice_in_dim(out, at, 1, axis=0))
        out = jax.lax.dynamic_update_slice_in_dim(out, row, at, axis=0)
    return out


def _pallas_wire(wire: str) -> bool:
    """Whether the flat wire codec runs the Pallas buffer kernels (True)
    or their pure-XLA oracle (False; the CPU default)."""
    if wire == "planar":
        return True
    if wire == "seq":
        return False
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Dense backend: x' = W @ Z (einsum over client axis). Reference semantics.
# ---------------------------------------------------------------------------

_F32_EXACT = jax.lax.Precision.HIGHEST


def mix_dense(W: np.ndarray, stacked: Pytree) -> Pytree:
    """Eq. 5 reference: x' = W @ z per leaf, f32 tensordot over the
    client axis (the bitwise target every other backend is tested
    against). HIGHEST precision: a TPU's default f32 matmul is one bf16
    pass, which would round every mixed parameter to ~3 digits."""
    Wj = jnp.asarray(W)

    def mx(z):
        out = jnp.tensordot(Wj.astype(jnp.float32), z.astype(jnp.float32),
                            axes=([1], [0]), precision=_F32_EXACT)
        return out.astype(z.dtype)

    return jax.tree.map(mx, stacked)


def _quant_leaf_keys(key: jax.Array, n_leaves: int, m: int) -> jax.Array:
    """The single source of truth for how a mixing key becomes per-leaf,
    per-client quantizer keys — shared by the dense reference and the
    sparse backend so both draw identical stochastic-rounding bits."""
    return jax.random.split(key, n_leaves * m).reshape(n_leaves, m, 2)


def _mix_dense_quantized(W: np.ndarray, x: Pytree, z: Pytree,
                         quant: QuantConfig, key: jax.Array,
                         leaf_keys: jax.Array | None = None) -> Pytree:
    """Eq. 7 with dense W: x + W @ Q(z - x), quantizing per client & leaf.

    ``leaf_keys`` [n_leaves, m, 2] overrides the in-place key derivation —
    the pooled cohort path derives keys at the FULL logical width and
    gathers the cohort's rows, so a [k, k] sub-mix draws bit-identical
    stochastic-rounding noise to the resident [m, m] mix.
    """
    Wj = jnp.asarray(W, dtype=jnp.float32)
    m = Wj.shape[0]
    leaves_x, treedef = jax.tree.flatten(x)
    leaves_z = treedef.flatten_up_to(z)
    n_leaves = len(leaves_x)
    if leaf_keys is not None:
        keys = leaf_keys
    else:
        keys = _quant_leaf_keys(key, n_leaves, m) \
            if (quant.stochastic and quant.enabled) else [[None] * m] * n_leaves

    out = []
    for li, (xl, zl) in enumerate(zip(leaves_x, leaves_z)):
        delta = (zl - xl).astype(jnp.float32)  # [m, ...]

        def qdq(d, k):
            code, s = quantize_int(d.reshape(-1), quant, k)
            return dequantize_int(code, s).reshape(d.shape)

        if quant.enabled:
            kvec = keys[li] if quant.stochastic else None
            q = (jax.vmap(qdq)(delta, kvec) if quant.stochastic
                 else jax.vmap(lambda d: qdq(d, None))(delta))
        else:
            q = delta
        if quant.delta_mode == "lemma5":
            # x' = W (x + q): the recursion the paper's proofs analyze.
            mixed = jnp.tensordot(Wj, xl.astype(jnp.float32) + q,
                                  axes=([1], [0]), precision=_F32_EXACT)
            out.append(mixed.astype(xl.dtype))
        else:
            # x' = x + W q: Algorithm 2 verbatim (needs PSD W, see docs).
            mixed = jnp.tensordot(Wj, q, axes=([1], [0]),
                                  precision=_F32_EXACT)
            out.append((xl.astype(jnp.float32) + mixed).astype(xl.dtype))
    return jax.tree.unflatten(treedef, out)


def _weighted_replica_base(xs, weights):
    """The ``lemma5`` base ``sum_k w_k * x_k`` over the received f32
    replica buffers: xs [..., K, per, W], weights [..., K]. Shared by the
    mesh body and the mesh-free reference so both accumulate in the same
    order (cross-module FMA contraction still allows ~1 ulp/term of
    drift — see ``dequant_mix_buffer_ref``)."""
    base = weights[..., 0, None, None] * xs[..., 0, :, :]
    for j in range(1, xs.shape[-3]):
        base = base + weights[..., j, None, None] * xs[..., j, :, :]
    return base


def _split_streams(streams, n_words: int, n_leaves: int, per: int,
                   lemma5: bool):
    """Received u32 streams (each [m_local, L]) -> their parts, each
    stacked over streams: packed words [m_local, K, W], per-leaf f32
    scales [m_local, K, nl] and, for ``lemma5``, the f32 replica buffers
    [m_local, K, per, W] (else None). Each stream is sliced before the
    stacking: parts sliced out of one stacked [m_local, K, L] buffer
    share its layout, and the TPU compiler lays that buffer out for the
    small scale block, padding the client and stream dims 64-fold."""
    words = jnp.stack([st[:, :n_words] for st in streams], axis=1)
    scales = jax.lax.bitcast_convert_type(
        jnp.stack([st[:, n_words:n_words + n_leaves] for st in streams],
                  axis=1), jnp.float32)
    xs = None
    if lemma5:
        xs = jnp.stack([rows_to_planar(jax.lax.bitcast_convert_type(
            st[:, n_words + n_leaves:], jnp.float32), per, n_words)
            for st in streams], axis=1)
    return words, scales, xs


def execute_plan_reference(plan: GossipPlan, W, stacked: Pytree,
                           x: Pytree | None = None,
                           quant: QuantConfig | None = None,
                           key: jax.Array | None = None) -> Pytree:
    """Mesh-free reference of the sparse backend's *math*: the same
    step/weight decomposition, with takes instead of ppermutes. Pins the
    IR semantics to ``mix_dense`` in tests without needing devices.

    With a ``quant`` config this is the SPEC of the flat wire path: the
    identical planar layout, per-leaf scales, shared stochastic-rounding
    key derivation, and accumulation order as the shard_map body — the
    mesh WIRE (packed words + scales) must match it bit for bit, and the
    fused float output to a few ulp (XLA's per-module FMA contraction is
    the only slack; see ``kernels.ref.dequant_mix_buffer_ref``). ``x`` is
    the held parameter state of eq. 7; ``key`` feeds stochastic rounding.
    """
    w_self, w_steps = plan.gather_weights(W)
    src = jnp.asarray(plan.src)
    live = [k for k in range(plan.n_steps) if plan.wire_pairs(k)]

    if quant is None or not quant.enabled:

        def mx(z):
            zf = z.astype(jnp.float32)
            bshape = (-1,) + (1,) * (zf.ndim - 1)
            acc = w_self.reshape(bshape) * zf
            for k in live:
                acc = acc + w_steps[k].reshape(bshape) * jnp.take(zf, src[k],
                                                                  axis=0)
            return acc.astype(z.dtype)

        return jax.tree.map(mx, stacked)

    # ---- quantized: the flat wire-buffer math, batched over clients ----
    if x is None:
        raise ValueError("quantized plan reference needs the held state x")
    m = plan.m
    layout = WireLayout.for_tree(jax.tree.map(lambda l: l[0], x),
                                 bits=quant.bits)
    X = layout.to_planar_stacked(x)              # [m, per, W]
    # Leaf-dtype subtraction before the f32 cast, like the mesh body and
    # the dense reference.
    delta = layout.to_planar_stacked(jax.tree.map(
        lambda zl, xl: zl - xl, stacked, x))
    scales = layout.leaf_scales(delta, quant)    # [m, n_leaves]
    leaf_keys = None
    if quant.stochastic:
        leaf_keys = _quant_leaf_keys(key, layout.n_leaves, m)
        if plan.lane_to_client is not None:
            # Placed plan: inputs are in LANE order, keys derive in
            # client order — lane p replays client lane_to_client[p]'s
            # draws, exactly like the mesh executor.
            leaf_keys = leaf_keys[:, jnp.asarray(plan.lane_to_client)]
    words = layout.encode(delta, scales, quant, leaf_keys=leaf_keys)

    ws = jnp.stack([w_self] + [w_steps[k] for k in live], axis=1)  # [m, K]
    streams = jnp.stack(
        [words] + [jnp.take(words, src[k], axis=0) for k in live], axis=1)
    scs = jnp.stack(
        [scales] + [jnp.take(scales, src[k], axis=0) for k in live], axis=1)
    lemma5 = quant.delta_mode == "lemma5"
    if lemma5:
        base_in = jnp.stack(
            [X] + [jnp.take(X, src[k], axis=0) for k in live], axis=1)
    else:
        base_in = X

    # One client at a time (lax.map), so the decode runs at the SAME
    # per-shard shapes as the mesh body — batching it over m would
    # compile a differently-vectorized accumulation and break bitwise
    # parity with the shard_map realization.
    def decode_one(args):
        s, sc, w, b = args
        base = _weighted_replica_base(b, w) if lemma5 else b
        return layout.decode_apply(base, s, sc, w, quant)

    out = jax.lax.map(decode_one, (streams, scs, ws, base_in))
    return layout.from_planar_stacked(out)


# ---------------------------------------------------------------------------
# Sparse backend: shard_map + masked ppermute, one client per shard
# ---------------------------------------------------------------------------

def _full_specs(tree: Pytree, client_axes: Sequence[str],
                param_specs: Pytree | None) -> Pytree:
    """PartitionSpecs for shard_map in/out. If the caller provided the
    model's param specs we reuse them (inner dims may be model-sharded);
    otherwise only the leading client axis is sharded."""
    ca = tuple(client_axes)
    if param_specs is not None:
        return param_specs
    return jax.tree.map(
        lambda leaf: P(ca, *([None] * (leaf.ndim - 1))), tree)


def _model_axes(mesh, client_axes: Sequence[str]) -> tuple:
    """Mesh axes that are NOT client axes — the tensor-parallel axes of a
    2D ``(clients, model)`` mesh (empty on the classic 1D client mesh)."""
    if mesh is None:
        return ()
    ca = tuple(client_axes)
    return tuple(a for a in mesh.axis_names if a not in ca)


def _specs_model_sharded(param_specs: Pytree | None,
                         model_axes: Sequence[str]) -> bool:
    """True when any param spec shards an inner dim over a model axis —
    i.e. the shard_map body will see model SLICES of the leaves, so the
    quantizer's amax must be all-reduced over the model axes and the
    stochastic noise must be sliced from the full leaf's draw."""
    if param_specs is None or not model_axes:
        return False
    maxes = set(model_axes)
    for spec in jax.tree.leaves(param_specs,
                                is_leaf=lambda s: isinstance(s, P)):
        for entry in spec:
            names = entry if isinstance(entry, tuple) else (entry,)
            if any(n in maxes for n in names):
                return True
    return False


def _model_shard_noise(x: Pytree, keys: jnp.ndarray, m: int) -> Pytree:
    """Stochastic-rounding noise for the 2D mesh, as a STACKED PYTREE in
    leaf geometry (same shapes as ``x``): each leaf is the FULL leaf's
    ``uniform(key_leaf_client, (n,))`` draw — identical bits to
    ``WireLayout.noise_stacked`` on the unsharded layout — reshaped to the
    leaf's array shape. Handing it to shard_map under the model-sharded
    param specs slices each device's model block in ARRAY geometry (a
    non-leading sharded dim is non-contiguous in flat order, so the planar
    buffer could not be sliced directly), which keeps 2D wire bits equal
    to 1D positionwise. ``keys`` [m, n_leaves, 2] uint32 (lane order, i.e.
    already gathered through ``lane_to_client`` for placed plans)."""
    leaves, treedef = jax.tree.flatten(x)
    out = []
    for li, xl in enumerate(leaves):
        shape = tuple(xl.shape[1:])
        n = int(np.prod(shape)) if shape else 1
        u = jax.vmap(lambda k, n=n: jax.random.uniform(
            k, (n,), jnp.float32))(keys[:, li])
        out.append(u.reshape((m,) + shape))
    return jax.tree.unflatten(treedef, out)


def _make_sparse_exec(plan: GossipPlan, mesh, client_axes: Sequence[str],
                      param_specs: Pytree | None,
                      quant: QuantConfig | None,
                      wire: str = "auto") -> Callable:
    """Compile ``plan`` to exec(x, z, w_self, w_steps, key) -> x'.

    w_self [m] / w_steps [n_steps, m] may be traced (per-round gathers
    from a sampled W_t) or constants (static specs); weight 0 masks a
    plan edge out of the round while the wire schedule stays fixed.

    The body runs on the FLAT WIRE BUFFER (``core.wire_layout``): the
    client-local pytree is flattened once, every plan step ppermutes ONE
    contiguous array for the whole model, and (when quantized) encode /
    fused decode-apply each run once per round. Per-leaf scales ride the
    u32 stream tail; the ``lemma5`` recursion additionally bitcasts the
    f32 replica buffer into the same stream, so every mode stays at one
    collective launch per plan step.

    This is the ONE sparse executor: every plan is compiled to a
    :class:`~repro.core.gossip_plan.BlockPlan` over ``n_shards = m /
    m_local`` shards (each shard a block of ``m_local`` lanes, the
    layout jax's leading-axis sharding produces) and realized block-wise
    — intra-block edges become on-device lane gathers (zero wire),
    boundary edges become shard-level masked ppermute sub-steps carrying
    only the crossing lanes. At ``m_local == 1`` (one client per shard)
    the blocks are single lanes, every plan step degenerates to exactly
    one width-1 boundary sub-step, and the realization is the historical
    one-permute-per-step program (the mesh HLO pins hold).

    PLACED plans (``plan.lane_to_client`` set by the placement pass)
    execute identically — the plan arrays are already conjugated into
    lane space; the only client-space input derived here, the per-(leaf,
    client) stochastic-rounding keys, is gathered through
    ``lane_to_client`` so lane ``p`` replays client ``perm[p]``'s exact
    draws and placed training stays bitwise-equal to unplaced.

    2D ``(clients, model)`` MESHES compose transparently: when
    ``param_specs`` shard inner dims over the mesh's non-client axes,
    each device's block tree holds only its model slice, the local
    :class:`WireLayout` is the per-shard wire, and the boundary
    ppermutes — still issued along the CLIENT axes only — ship just that
    slice, so per-device wire drops ~linearly with model parallelism.
    Two cross-shard fixups keep 2D bitwise-equal to 1D: per-leaf amaxes
    are ``lax.pmax``-all-reduced over the model axes before becoming
    quantizer scales (max is order-exact), and stochastic-rounding noise
    is drawn from the FULL leaf's PRNG stream outside the shard_map and
    sliced per shard in leaf geometry (:func:`_model_shard_noise`).
    """
    ca = tuple(client_axes)
    m_local = _clients_per_shard(mesh, ca, plan.m)
    if m_local is None:
        raise ValueError(
            f"sparse mixer needs a mesh carrying a client block per "
            f"shard: plan has m={plan.m}, mesh axes {ca!r} must multiply "
            f"to a divisor of it")
    n_shards = plan.m // m_local
    bp = plan.block_plan(n_shards)
    axis = ca[0] if len(ca) == 1 else ca
    live = [k for k in range(plan.n_steps) if plan.wire_pairs(k)]
    w_specs = (P(ca), P(None, ca))
    maxes = _model_axes(mesh, ca)
    sharded2d = _specs_model_sharded(param_specs, maxes)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    intra_t = {k: jnp.asarray(bp.intra_src[k]) for k in live}
    sub_t = {k: [(sub, jnp.asarray(sub.send_lanes),
                  jnp.asarray(sub.recv_lanes)) for sub in bp.substeps[k]]
             for k in live}

    def sid():
        idx = jax.lax.axis_index(ca[0])
        for a in ca[1:]:
            idx = idx * sizes[a] + jax.lax.axis_index(a)
        return idx

    def issue_recvs(rows, s):
        """Issue EVERY live step's boundary ppermutes up front: rows
        [m_local, ...] (any per-lane payload — f32 rows or packed u32
        streams). All sends gather from the same `rows` (a dataflow
        antichain), so the collectives overlap each other and whatever
        compute runs between issue and combine (collective-matmul
        idiom). Returns {step: [received buffers per sub-step]}."""
        return {k: [jax.lax.ppermute(_take_lanes(rows, send[s]), axis,
                                     sub.pairs)
                    for sub, send, _ in sub_t[k]] for k in live}

    def combine_recv(rows, got_k, k, s):
        """Step k's receive for this shard: intra lanes gather locally;
        boundary lanes scatter the already-issued sub-step transfers
        over the identity gather (padded rows drop)."""
        out = _take_lanes(rows, intra_t[k][s])
        for (sub, send, recv), got in zip(sub_t[k], got_k):
            out = _put_lanes(out, recv[s], got)
        return out

    if quant is None or not quant.enabled:

        def body(z_blocks, wself, wsteps):
            with jax.named_scope("wire/exchange"):
                s = sid()
            layout = WireLayout.for_tree(
                jax.tree.map(lambda a: a[0], z_blocks))
            with jax.named_scope("wire/pack"):
                rows = jax.vmap(layout.flatten_f32)(z_blocks)  # [m_local, n]
            with jax.named_scope("wire/exchange"):
                got = issue_recvs(rows, s)
            # The f32 wire has no codec: its decode is the weighted sum.
            with jax.named_scope("wire/decode"):
                acc = wself[:, None] * rows
            for k in live:
                with jax.named_scope("wire/decode"):
                    w_k = wsteps[k][:, None]
                with jax.named_scope("wire/exchange"):
                    recv = combine_recv(rows, got[k], k, s)
                with jax.named_scope("wire/decode"):
                    acc = acc + w_k * recv
            with jax.named_scope("wire/unpack"):
                return jax.vmap(layout.unflatten)(acc)

        def ex(x, z, wself, wsteps, key=None):
            del x, key
            specs = _full_specs(z, ca, param_specs)
            # 2D: leaves the rules leave replicated come out identical on
            # every model column (client-axis-only collectives), but the
            # static replication checker can't see through the ppermutes
            # — turn it off rather than weaken the specs.
            fn = jax.shard_map(body, mesh=mesh,
                               in_specs=(specs,) + w_specs, out_specs=specs,
                               check_vma=not sharded2d)
            with jax.named_scope("wire/exchange"):
                wself = jnp.asarray(wself, jnp.float32)
                wsteps = jnp.asarray(wsteps, jnp.float32)
            return fn(z, wself, wsteps)

        return ex

    lemma5 = quant.delta_mode == "lemma5"
    pallas = _pallas_wire(wire)
    use_noise_input = sharded2d and quant.stochastic

    def q_body(x_blocks, z_blocks, keys_blk, wself, wsteps, *noise_in):
        with jax.named_scope("wire/exchange"):
            s = sid()
        layout = WireLayout.for_tree(jax.tree.map(lambda a: a[0], x_blocks),
                                     bits=quant.bits)
        nl, W = layout.n_leaves, layout.total_words
        with jax.named_scope("wire/pack"):
            x2d = layout.to_planar_stacked(x_blocks)  # [m_local, per, W]
            # Leaf-dtype subtraction before the f32 cast — the dense
            # reference's (z - x).astype(f32) semantics.
            delta = layout.to_planar_stacked(jax.tree.map(
                lambda zl, xl: zl - xl, z_blocks, x_blocks))
        with jax.named_scope("wire/scales"):
            if sharded2d and quant.scale_mode != "fixed":
                # Model-sharded leaves: the local amax covers only this
                # device's slice — all-reduce it over the model axes so
                # every shard derives the IDENTICAL per-leaf scale (max
                # is order-exact: bitwise equal to the 1D layout's scale).
                amax = jax.lax.pmax(layout.leaf_amax(delta), maxes)
                scales = layout.scales_from_amax(amax, quant)
            else:
                scales = layout.leaf_scales(delta, quant)  # [m_local, nl]
        with jax.named_scope("wire/encode/noise"):
            leaf_keys = (jnp.transpose(keys_blk, (1, 0, 2))
                         if quant.stochastic else None)  # [nl, m_local, 2]
            noise2d = (layout.to_planar_stacked(noise_in[0])
                       if noise_in else None)
        words = layout.encode(delta, scales, quant, leaf_keys=leaf_keys,
                              pallas=pallas, noise=noise2d)  # [m_local, W]
        with jax.named_scope("wire/stream"):
            tail = [jax.lax.bitcast_convert_type(scales, jnp.uint32)]
            if lemma5:
                tail.append(jax.lax.bitcast_convert_type(
                    x2d.reshape(m_local, -1), jnp.uint32))
            stream = jnp.concatenate([words] + tail, axis=1)  # [m_local, L]
        with jax.named_scope("wire/exchange"):
            got = issue_recvs(stream, s)
            streams = [stream] + [combine_recv(stream, got[k], k, s)
                                  for k in live]
        with jax.named_scope("wire/stream"):
            wlist = [wself] + [wsteps[k] for k in live]
            weights = jnp.stack(wlist, axis=1)        # [m_local, K]
            words_all, scales_all, xs = _split_streams(streams, W, nl,
                                                       layout.per, lemma5)
            base = _weighted_replica_base(xs, weights) if lemma5 else x2d
        out = layout.decode_apply(base, words_all, scales_all, weights,
                                  quant, pallas=pallas)
        with jax.named_scope("wire/unpack"):
            return layout.from_planar_stacked(out)

    def ex(x, z, wself, wsteps, key):
        specs = _full_specs(x, ca, param_specs)
        n_leaves = len(jax.tree.leaves(x))
        with jax.named_scope("wire/encode/noise"):
            if quant.stochastic:
                keys = jnp.transpose(
                    _quant_leaf_keys(key, n_leaves, plan.m),
                    (1, 0, 2))                        # [m(client), nl, 2]
                if plan.lane_to_client is not None:
                    # Lane p replays client lane_to_client[p]'s exact
                    # draws — key derivation stays in CLIENT space
                    # (single source of truth), so placed == unplaced
                    # bitwise.
                    keys = keys[jnp.asarray(plan.lane_to_client)]
            else:
                keys = jnp.zeros((plan.m, 1, 2), jnp.uint32)
            if use_noise_input:
                # 2D mesh: draw the FULL leaves' rounding noise here
                # (where the unsharded geometry is known) and let
                # shard_map slice each device's model block via the
                # param specs.
                extra = (_model_shard_noise(x, keys, plan.m),)
                extra_specs = (specs,)
            else:
                extra, extra_specs = (), ()
        # pallas_call has no replication rule, so the planar-wire body runs
        # with the checker off.
        fn = jax.shard_map(q_body, mesh=mesh,
                           in_specs=(specs, specs, P(ca, None, None))
                           + w_specs + extra_specs,
                           out_specs=specs,
                           check_vma=not (pallas or sharded2d))
        with jax.named_scope("wire/exchange"):
            wself = jnp.asarray(wself, jnp.float32)
            wsteps = jnp.asarray(wsteps, jnp.float32)
        return fn(x, z, keys, wself, wsteps, *extra)

    return ex


def make_plan_mixer(plan: GossipPlan, mesh,
                    client_axes: Sequence[str] = ("clients",),
                    param_specs: Pytree | None = None,
                    quant: QuantConfig | None = None,
                    wire: str = "auto") -> Callable:
    """Static plan (baked weights) -> mixer(x, z, key=None, t=None) -> x'.

    This is the sparse realization of ANY static MixingSpec: ring and
    torus lower to their shift decompositions, arbitrary graphs to
    matchings (see ``gossip_plan``). Quantized plans move packed words.
    """
    w_self, w_steps = plan.static_weights()
    ex = _make_sparse_exec(plan, mesh, client_axes, param_specs, quant,
                           wire=wire)

    def mixer(x: Pytree, z: Pytree, key=None, t=None) -> Pytree:
        del t
        return ex(x, z, w_self, w_steps, key)

    return mixer


# ---------------------------------------------------------------------------
# Event mixer: one mixing event with an externally supplied W
# ---------------------------------------------------------------------------

def make_event_mixer(m: int, quant: QuantConfig | None = None, mesh=None,
                     client_axes: Sequence[str] = ("clients",),
                     param_specs: Pytree | None = None,
                     plan: GossipPlan | None = None,
                     wire: str = "auto", gate: bool = True) -> Callable:
    """Build mix_event(x, z, W, active, key) -> x' for *externally sampled*
    mixing events.

    Unlike :func:`make_scheduled_mixer` (which derives ``W_t`` from a
    round counter inside the mixer), the caller hands over the event's
    (possibly traced) ``W`` [m, m] row-stochastic matrix and the ``active``
    [m] participation mask each call. This is the layer both *stateful*
    topologies (the in-graph random-walk token) and the asynchronous
    gossip engine (staleness-reweighted ``W_eff``) inject their matrices
    through.

    Backend: ``plan=None`` runs the dense reference (einsum / quantized
    dense recursion, any W); a :class:`GossipPlan` runs the sparse masked-
    ppermute backend — ``W``'s off-diagonal support must lie inside the
    plan's support graph (weights are *gathered* onto the fixed wire).
    ``gate=False`` skips the inactive-client z gating (callers whose
    events never sideline clients).
    """
    def z_gate(active, z, x):
        if not gate:
            return z

        def per_leaf(zl, xl):
            mask = active.reshape((-1,) + (1,) * (zl.ndim - 1))
            return jnp.where(mask > 0, zl, xl)
        return jax.tree.map(per_leaf, z, x)

    if plan is not None:
        if plan.m != m:
            raise ValueError(f"plan has m={plan.m}, expected {m}")
        ex = _make_sparse_exec(plan, mesh, client_axes, param_specs, quant,
                               wire=wire)

        def mix_event(x, z, W, active, key=None):
            with jax.named_scope("wire/exchange"):
                w_self, w_steps = plan.gather_weights(W)
            with jax.named_scope("wire/pack"):
                z_eff = z_gate(active, z, x)
            return ex(x, z_eff, w_self, w_steps, key)

        return mix_event

    def mix_event(x, z, W, active, key=None):
        z_eff = z_gate(active, z, x)
        if quant is None or not quant.enabled:
            return mix_dense(W, z_eff)
        return _mix_dense_quantized(W, x, z_eff, quant, key)

    return mix_event


# ---------------------------------------------------------------------------
# Fused-round tail: deferred last two local steps + wire + mix, overlapped
# ---------------------------------------------------------------------------

def make_fused_tail(loss_fn, m: int, *, eta: float, theta: float,
                    quant: QuantConfig | None = None, mesh=None,
                    client_axes: Sequence[str] = ("clients",),
                    param_specs: Pytree | None = None,
                    plan: GossipPlan | None = None, wire: str = "auto",
                    gate: bool = True) -> Callable:
    """Fused-round tail: the round's last two local steps, the wire
    encode, every plan step's ppermute, and the combined decode-apply in
    ONE overlapped stage (see ``DFedAvgMConfig.fuse_round``).

    The returned
    ``tail(x, y, v, g, batch_last, keys_last, key_q, active, W)``
    consumes :func:`~repro.core.local_sgd.local_train_deferred`'s output
    (``y``/``v``/``g`` the round's un-applied penultimate step, stacked
    over clients) and runs, per client:

      1. SEND — one fused pass applies ``v' = theta*v - eta*g;
         y' = y + v'`` and emits ``pack(Q(y' - x))`` as a SIDE OUTPUT
         (``WireLayout.encode_momentum``): the wire buffer never costs
         its own trip over the model. The published ``z`` is ``y'``.
      2. Every plan step's masked ppermute issues immediately — the
         sends all read the same stream, a dataflow antichain.
      3. OVERLAP WINDOW — the round's LAST gradient ``g_K = grad(y')``
         computes between issue and decode, so on hardware with async
         collectives the wire flies behind it.
      4. RECEIVE — one fused pass mixes the received streams AND applies
         the deferred last update (``WireLayout.decode_apply_momentum``):
         ``x' = [base + sum_k w_k*deq(stream_k)] + (theta*v' - eta*g_K)``
         — mix -> v' -> y' in a single read/write of the model.

    Relative to the unfused round this defers ONE local step past the
    mix — neighbors see ``y_{K-1}``, not ``y_K`` — trading one step of
    wire freshness for full wire/compute overlap. It is an algorithm
    VARIANT, not a bit-compatible rewrite; at ``eta == 0`` the deferred
    updates vanish and the two rounds coincide bitwise (pinned in
    ``tests/test_fused_round.py``). Inactive clients (``gate=True``)
    gate to ``y = x, v = g = 0`` before the encode, so they publish
    ``Q(0)``, apply a zero deferred update, and are held exactly.

    Backend mirrors :func:`make_event_mixer`: ``plan=None`` is the dense
    reference (einsum mix, any ``W``); a :class:`GossipPlan` runs the
    sparse masked-ppermute realization (one-client-per-shard or
    block-sharded). Returns ``(x_next, y_pub, loss_last)``: ``y_pub``
    the published z (consensus-drift metric), ``loss_last`` [m] the last
    step's per-client losses.
    """
    grad_fn = jax.value_and_grad(loss_fn)
    eta_f = jnp.float32(eta)
    theta_f = jnp.float32(theta)
    quant_on = quant is not None and quant.enabled

    def _gate0(tree, active):
        return jax.tree.map(
            lambda l: (l * active.reshape((-1,) + (1,) * (l.ndim - 1)))
            .astype(l.dtype), tree)

    if plan is None:
        # ---- dense reference: tree-level, any (traced) W ----
        def tail(x, y, v, g, batch_last, keys_last, key_q, active, W):
            if gate:
                y = jax.tree.map(
                    lambda yl, xl: jnp.where(
                        active.reshape((-1,) + (1,) * (yl.ndim - 1)) > 0,
                        yl, xl), y, x)
                v, g = _gate0(v, active), _gate0(g, active)
            v1 = jax.tree.map(
                lambda vl, gl: theta_f * vl.astype(jnp.float32)
                - eta_f * gl.astype(jnp.float32), v, g)
            y1 = jax.tree.map(
                lambda yl, vl: (yl.astype(jnp.float32) + vl)
                .astype(yl.dtype), y, v1)
            loss_last, gK = jax.vmap(grad_fn)(y1, batch_last, keys_last)
            if gate:
                gK = _gate0(gK, active)
            mixed = (_mix_dense_quantized(W, x, y1, quant, key_q)
                     if quant_on else mix_dense(W, y1))
            x_next = jax.tree.map(
                lambda ml, vl, gl: (ml.astype(jnp.float32) + theta_f * vl
                                    - eta_f * gl.astype(jnp.float32))
                .astype(ml.dtype), mixed, v1, gK)
            return x_next, y1, loss_last

        return tail

    # ---- sparse: shard_map + masked ppermutes, stacked [m_local] form ----
    if plan.m != m:
        raise ValueError(f"plan has m={plan.m}, expected {m}")
    ca = tuple(client_axes)
    m_local = _clients_per_shard(mesh, ca, m)
    if m_local is None:
        raise ValueError(
            f"fused sparse tail needs a mesh carrying a client block per "
            f"shard: m={m}, client_axes={ca!r}")
    if _specs_model_sharded(param_specs, _model_axes(mesh, ca)):
        raise ValueError(
            "fuse_round is not supported with model-sharded params on a "
            "2D (clients, model) mesh: the fused tail computes the "
            "round's last gradient INSIDE the client shard_map body, "
            "which would see only this device's model slice of the "
            "params. Run the unfused round (fuse_round=False) — its "
            "local SGD runs outside the mixer under GSPMD, which "
            "partitions the loss over the model axis automatically.")
    axis = ca[0] if len(ca) == 1 else ca
    pairs = [plan.wire_pairs(k) for k in range(plan.n_steps)]
    live = [k for k in range(plan.n_steps) if pairs[k]]
    pallas = _pallas_wire(wire)
    lemma5 = quant_on and quant.delta_mode == "lemma5"

    # ONE realization for every shard width: the compiled BlockPlan's
    # intra gathers + boundary sub-step ppermutes (at m_local == 1 each
    # plan step is exactly one width-1 sub-step — the historical
    # one-permute-per-step program, pinned by the mesh HLO tests).
    bp = plan.block_plan(plan.m // m_local)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    intra_t = {k: jnp.asarray(bp.intra_src[k]) for k in live}
    sub_t = {k: [(sub, jnp.asarray(sub.send_lanes),
                  jnp.asarray(sub.recv_lanes)) for sub in bp.substeps[k]]
             for k in live}

    def sid():
        idx = jax.lax.axis_index(ca[0])
        for a in ca[1:]:
            idx = idx * sizes[a] + jax.lax.axis_index(a)
        return idx

    def issue_steps(stream, s):
        # All sends read `stream` — a dataflow antichain; the
        # boundary collectives overlap each other and the gradient
        # computed between issue and combine.
        return {k: [jax.lax.ppermute(_take_lanes(stream, send[s]), axis,
                                     sub.pairs)
                    for sub, send, _ in sub_t[k]] for k in live}

    def combine_step(stream, got_k, k, s):
        out = _take_lanes(stream, intra_t[k][s])
        for (sub, send, recv), got in zip(sub_t[k], got_k):
            out = _put_lanes(out, recv[s], got)
        return out

    if not quant_on:
        # fp32 wire: the fused update+publish and mix+deferred-update are
        # plain XLA elementwise chains (XLA fuses them natively — the
        # Pallas kernels exist for the quantized wire); the overlap
        # structure is identical to the quantized body.
        def body(x_bl, y_bl, v_bl, g_bl, batch_bl, klast_bl, wself, wsteps,
                 act):
            with jax.named_scope("wire/exchange"):
                s = sid()
            layout = WireLayout.for_tree(jax.tree.map(lambda a: a[0], x_bl))
            # Tree-level penultimate step: only the published z ever gets
            # flattened to a wire row (same layout traffic as the unfused
            # round); XLA fuses the elementwise chains.
            if gate:
                with jax.named_scope("wire/pack"):
                    y_bl = jax.tree.map(
                        lambda yl, xl: jnp.where(
                            act.reshape((-1,) + (1,) * (yl.ndim - 1)) > 0,
                            yl, xl), y_bl, x_bl)
                    v_bl, g_bl = _gate0(v_bl, act), _gate0(g_bl, act)
            with jax.named_scope("sgd/update"):
                v1 = jax.tree.map(
                    lambda vl, gl: theta_f * vl.astype(jnp.float32)
                    - eta_f * gl.astype(jnp.float32), v_bl, g_bl)
                y1 = jax.tree.map(
                    lambda yl, vl: (yl.astype(jnp.float32) + vl)
                    .astype(yl.dtype), y_bl, v1)
            with jax.named_scope("wire/pack"):
                z = jax.vmap(layout.flatten_f32)(y1)    # published y_{K-1}
            with jax.named_scope("wire/exchange"):
                got = issue_steps(z, s)
            # ---- overlap window: the last gradient computes while the
            # wire flies — nothing below reads a received buffer until
            # the weighted combine.
            with jax.named_scope("sgd/grad"):
                loss_last, gK = jax.vmap(grad_fn)(y1, batch_bl, klast_bl)
            if gate:
                with jax.named_scope("sgd/update"):
                    gK = _gate0(gK, act)
            # The f32 wire has no codec: its decode is the weighted sum.
            with jax.named_scope("wire/decode"):
                acc = wself[:, None] * z
            for k in live:
                with jax.named_scope("wire/decode"):
                    w_k = wsteps[k][:, None]
                with jax.named_scope("wire/exchange"):
                    recv = combine_step(z, got[k], k, s)
                with jax.named_scope("wire/decode"):
                    acc = acc + w_k * recv
            with jax.named_scope("wire/unpack"):
                mixed = jax.vmap(layout.unflatten)(acc)
            with jax.named_scope("sgd/update"):
                x_next = jax.tree.map(
                    lambda ml, vl, gl: (ml.astype(jnp.float32)
                                        + theta_f * vl
                                        - eta_f * gl.astype(jnp.float32))
                    .astype(ml.dtype), mixed, v1, gK)
            return x_next, y1, loss_last

        def tail(x, y, v, g, batch_last, keys_last, key_q, active, W):
            del key_q
            with jax.named_scope("wire/exchange"):
                w_self, w_steps = plan.gather_weights(W)
            specs = _full_specs(x, ca, param_specs)
            bspecs = _full_specs(batch_last, ca, None)
            fn = jax.shard_map(body, mesh=mesh,
                               in_specs=(specs, specs, specs, specs, bspecs,
                                         P(ca, None), P(ca), P(None, ca),
                                         P(ca)),
                               out_specs=(specs, specs, P(ca)))
            with jax.named_scope("wire/exchange"):
                w_self = jnp.asarray(w_self, jnp.float32)
                w_steps = jnp.asarray(w_steps, jnp.float32)
                active = jnp.asarray(active, jnp.float32)
            return fn(x, y, v, g, batch_last, keys_last, w_self, w_steps,
                      active)

        return tail

    def q_body(x_bl, y_bl, v_bl, g_bl, batch_bl, klast_bl, keys_blk,
               wself, wsteps, act):
        with jax.named_scope("wire/exchange"):
            s = sid()
        layout = WireLayout.for_tree(jax.tree.map(lambda a: a[0], x_bl),
                                     bits=quant.bits)
        nl, Wd = layout.n_leaves, layout.total_words
        with jax.named_scope("wire/pack"):
            x2d = layout.to_planar_stacked(x_bl)    # [m_local, per, W]
        m_loc = x2d.shape[0]
        with jax.named_scope("wire/encode/noise"):
            leaf_keys = (jnp.transpose(keys_blk, (1, 0, 2))
                         if quant.stochastic else None)
        if pallas:
            # Kernel path: y/v/g are staged planar so the fused kernels
            # stream them — the penultimate update + pack is ONE pass,
            # mix + deferred update is ONE pass.
            with jax.named_scope("wire/pack"):
                y2d = layout.to_planar_stacked(y_bl)
                v2d = layout.to_planar_stacked(v_bl)
                g2d = layout.to_planar_stacked(g_bl)
                if gate:
                    am = act[:, None, None]
                    y2d = jnp.where(am > 0, y2d, x2d)
                    v2d = v2d * am
                    g2d = g2d * am
            with jax.named_scope("wire/encode"):
                et = jnp.tile(jnp.stack([eta_f, theta_f])[None],
                              (m_loc, 1))
            # Scales of the RESULTING delta, same expression order as the
            # fused kernel — a reduction, not another full-size buffer
            # pass.
            with jax.named_scope("wire/scales"):
                delta = (y2d + (theta_f * v2d - eta_f * g2d)) - x2d
                scales = layout.leaf_scales(delta, quant)  # [m_local, nl]
            # SEND: apply the penultimate step and emit the wire words as
            # a side output of the same pass.
            y_out, v_out, words = layout.encode_momentum(
                y2d, v2d, g2d, x2d, scales, et, quant,
                leaf_keys=leaf_keys, pallas=True)
        else:
            # Oracle path (CPU/seq wire): the same math at TREE level —
            # XLA fuses the elementwise chains the Pallas kernels fuse by
            # hand, and only z and x ever get planar-staged, matching the
            # unfused round's layout traffic.
            if gate:
                with jax.named_scope("wire/pack"):
                    y_bl = jax.tree.map(
                        lambda yl, xl: jnp.where(
                            act.reshape((-1,) + (1,) * (yl.ndim - 1)) > 0,
                            yl, xl), y_bl, x_bl)
                    v_bl, g_bl = _gate0(v_bl, act), _gate0(g_bl, act)
            with jax.named_scope("sgd/update"):
                v1 = jax.tree.map(
                    lambda vl, gl: theta_f * vl.astype(jnp.float32)
                    - eta_f * gl.astype(jnp.float32), v_bl, g_bl)
                y1 = jax.tree.map(
                    lambda yl, vl: (yl.astype(jnp.float32) + vl)
                    .astype(yl.dtype), y_bl, v1)
            with jax.named_scope("wire/pack"):
                z2d = layout.to_planar_stacked(y1)
                delta = z2d - x2d
            with jax.named_scope("wire/scales"):
                scales = layout.leaf_scales(delta, quant)  # [m_local, nl]
            words = layout.encode(delta, scales, quant,
                                  leaf_keys=leaf_keys, pallas=False)
        with jax.named_scope("wire/stream"):
            tail_ = [jax.lax.bitcast_convert_type(scales, jnp.uint32)]
            if lemma5:
                tail_.append(jax.lax.bitcast_convert_type(
                    x2d.reshape(m_loc, -1), jnp.uint32))
            stream = jnp.concatenate([words] + tail_, axis=1)  # [m_loc, L]
        with jax.named_scope("wire/exchange"):
            got = issue_steps(stream, s)
        # ---- overlap window: the round's LAST gradient computes while
        # the wire flies — nothing below touches a received stream until
        # the fused decode.
        with jax.named_scope("wire/unpack"):
            y_pub = layout.from_planar_stacked(y_out) if pallas else y1
        with jax.named_scope("sgd/grad"):
            loss_last, gK = jax.vmap(grad_fn)(y_pub, batch_bl, klast_bl)
        if pallas:
            with jax.named_scope("wire/pack"):
                gK2d = layout.to_planar_stacked(gK)
                if gate:
                    gK2d = gK2d * act[:, None, None]
        elif gate:
            with jax.named_scope("sgd/update"):
                gK = _gate0(gK, act)
        with jax.named_scope("wire/exchange"):
            streams = [stream] + [combine_step(stream, got[k], k, s)
                                  for k in live]
        with jax.named_scope("wire/stream"):
            wlist = [wself] + [wsteps[k] for k in live]
            weights = jnp.stack(wlist, axis=1)      # [m_local, K]
            words_all, scales_all, xs = _split_streams(streams, Wd, nl,
                                                       layout.per, lemma5)
            base = _weighted_replica_base(xs, weights) if lemma5 else x2d
        # RECEIVE: mix + deferred last update in one fused pass (kernel
        # path) / one XLA-fused chain (oracle path).
        if pallas:
            out2d = layout.decode_apply_momentum(
                base, words_all, scales_all, weights, v_out, gK2d, et,
                quant, pallas=True)
            with jax.named_scope("wire/unpack"):
                return layout.from_planar_stacked(out2d), y_pub, loss_last
        out2d = layout.decode_apply(base, words_all, scales_all, weights,
                                    quant, pallas=False)
        with jax.named_scope("wire/unpack"):
            mixed = layout.from_planar_stacked(out2d)
        with jax.named_scope("sgd/update"):
            x_next = jax.tree.map(
                lambda ml, vl, gl: (ml.astype(jnp.float32) + theta_f * vl
                                    - eta_f * gl.astype(jnp.float32))
                .astype(ml.dtype), mixed, v1, gK)
        return x_next, y_pub, loss_last

    def tail(x, y, v, g, batch_last, keys_last, key_q, active, W):
        with jax.named_scope("wire/exchange"):
            w_self, w_steps = plan.gather_weights(W)
        specs = _full_specs(x, ca, param_specs)
        bspecs = _full_specs(batch_last, ca, None)
        n_leaves = len(jax.tree.leaves(x))
        with jax.named_scope("wire/encode/noise"):
            if quant.stochastic:
                keys = jnp.transpose(_quant_leaf_keys(key_q, n_leaves, m),
                                     (1, 0, 2))     # [m(client), nl, 2]
                if plan.lane_to_client is not None:
                    # Placed plan: lane p replays client
                    # lane_to_client[p]'s draws (client-space key
                    # derivation, like the unfused executor) — placed ==
                    # unplaced bitwise.
                    keys = keys[jnp.asarray(plan.lane_to_client)]
            else:
                keys = jnp.zeros((m, 1, 2), jnp.uint32)
        fn = jax.shard_map(q_body, mesh=mesh,
                           in_specs=(specs, specs, specs, specs, bspecs,
                                     P(ca, None), P(ca, None, None), P(ca),
                                     P(None, ca), P(ca)),
                           out_specs=(specs, specs, P(ca)),
                           check_vma=not pallas)
        with jax.named_scope("wire/exchange"):
            w_self = jnp.asarray(w_self, jnp.float32)
            w_steps = jnp.asarray(w_steps, jnp.float32)
            active = jnp.asarray(active, jnp.float32)
        return fn(x, y, v, g, batch_last, keys_last, keys, w_self, w_steps,
                  active)

    return tail


# ---------------------------------------------------------------------------
# Scheduled mixer: time-varying W_t sampled per round, either backend
# ---------------------------------------------------------------------------

def make_scheduled_mixer(schedule: TopologySchedule, cfg: MixerConfig,
                         mesh=None,
                         client_axes: Sequence[str] = ("clients",),
                         param_specs: Pytree | None = None,
                         placement=None) -> Callable:
    """Build mixer(x, z, key, t) -> (x', active) for a time-varying
    topology.

    Per round: ``(W_t, active) = schedule.round_event(key, t)`` is computed
    *in-graph* (so the loop stays jittable), inactive clients' fresh ``z``
    is gated back to their held ``x`` (they "send nothing" — their column
    of W_t is zero for every active row, and their own row is ``e_i``),
    then gossip runs with the sampled matrix through the chosen backend:

      unquantized (eq. 5):  x' = W_t @ z_eff
      quantized   (eq. 7):  x' = x + W_t @ Q(z_eff - x)   (or the lemma5
                            recursion x' = W_t @ (x + Q(z_eff - x)))

    Backends: ``dense`` einsum (any W_t, all-gather traffic) or ``sparse``
    — the schedule's support graph compiles once to a GossipPlan and each
    round's W_t only *gathers weights* onto the fixed masked-ppermute
    schedule, so edge-sampled / partial / cycle rounds move O(degree)
    neighbor bytes instead of O(m). ``auto`` picks sparse when the mesh
    has one client per shard. Inactive clients quantize Q(0) = 0, so both
    quantized recursions hold them exactly.

    Caveat (same as the static path, see QuantConfig.delta_mode): the
    ``eq7`` recursion is only stable for PSD mixing matrices, and sampled
    W_t (Metropolis on a random subgraph) are NOT guaranteed PSD — prefer
    the default ``lemma5`` mode with stochastic schedules.

    ``placement`` (a ``gossip_plan.Placement``, sparse impl only) runs
    the support plan placed — client state lives in lane order, so the
    schedule's client-order ``active`` mask is gathered to lane order
    both for gating and in the returned tuple.
    """
    if cfg.impl not in ("auto", "dense", "sparse"):
        raise ValueError("time-varying schedules support impl 'dense', "
                         f"'sparse' or 'auto', got impl={cfg.impl!r}")
    impl = cfg.resolved_impl(schedule, mesh, client_axes)
    quant = cfg.quant
    if placement is not None and impl != "sparse":
        raise ValueError(
            f"placement requires the sparse backend, got impl={impl!r}")

    if impl == "sparse" and schedule.kind == "cycle":
        return _make_cycle_switch_mixer(schedule, cfg, mesh, client_axes,
                                        param_specs, placement=placement)

    plan = schedule.gossip_plan() if impl == "sparse" else None
    if plan is not None and placement is not None:
        plan = plan.placed(placement)
    ev = make_event_mixer(schedule.m, quant=quant, mesh=mesh,
                          client_axes=client_axes, param_specs=param_specs,
                          plan=plan, wire=cfg.wire,
                          gate=schedule.gates_participation)
    perm = (None if placement is None or placement.is_identity
            else jnp.asarray(placement.perm))

    def mixer(x: Pytree, z: Pytree, key: jax.Array, t
              ) -> tuple[Pytree, jnp.ndarray]:
        with jax.named_scope("wire/exchange"):
            W_t, active, key_q = schedule.round_event(key, t)
            if perm is not None:
                active = active[perm]
        return ev(x, z, W_t, active, key_q), active

    return mixer


def _make_cycle_switch_mixer(schedule: TopologySchedule, cfg: MixerConfig,
                             mesh, client_axes: Sequence[str],
                             param_specs: Pytree | None,
                             placement=None) -> Callable:
    """Dynamic-plan sparse realization of a deterministic cycle: compile
    one static :class:`GossipPlan` PER MEMBER and ``lax.switch`` on
    ``t mod n`` between their shard_map bodies, so each round only moves
    its own member's wire edges. The union-support plan used to ship every
    member's edges every round and mask the off-cycle ones to weight 0 —
    for members with disjoint supports that is strictly wasted wire
    (see ``plan_round_bits`` with a plan list for the billing side).
    ``placement`` (computed on the UNION support) places every member
    plan with the same lane relabeling."""
    plans = schedule.gossip_plans()
    if placement is not None:
        plans = [p.placed(placement) for p in plans]
    quant = cfg.quant
    execs = [_make_sparse_exec(p, mesh, client_axes, param_specs, quant,
                               wire=cfg.wire) for p in plans]
    weights = [p.static_weights() for p in plans]
    n = len(plans)
    ones = jnp.ones((schedule.m,), jnp.float32)

    def mixer(x: Pytree, z: Pytree, key: jax.Array, t
              ) -> tuple[Pytree, jnp.ndarray]:
        branches = [
            (lambda ops, ex=ex, ws=ws: ex(ops[0], ops[1], ws[0], ws[1],
                                          ops[2]))
            for ex, ws in zip(execs, weights)]
        idx = jnp.asarray(t, jnp.int32) % n
        return jax.lax.switch(idx, branches, (x, z, key)), ones

    return mixer


# ---------------------------------------------------------------------------
# Static ring / torus: thin plan instances (kept as named constructors)
# ---------------------------------------------------------------------------

def make_ring_mixer(spec: MixingSpec, mesh,
                    client_axes: Sequence[str] = ("clients",),
                    param_specs: Pytree | None = None,
                    quant: QuantConfig | None = None) -> Callable:
    """Ring gossip as a 2-step shift plan over the sparse backend."""
    if spec.kind != "ring":
        raise ValueError("ring mixer needs a ring MixingSpec")
    return make_plan_mixer(spec.gossip_plan(), mesh, client_axes,
                           param_specs=param_specs, quant=quant)


def make_torus_mixer(spec: MixingSpec, mesh,
                     client_axes: Sequence[str] = ("clients",),
                     param_specs: Pytree | None = None,
                     quant: QuantConfig | None = None) -> Callable:
    """Torus gossip as a 4-shift plan over the sparse backend (2-D TPU
    mesh native: with client axes (rows, cols) each shift is one ICI
    neighbor hop)."""
    if spec.kind != "torus":
        raise ValueError("torus mixer needs a torus MixingSpec")
    return make_plan_mixer(spec.gossip_plan(), mesh, client_axes,
                           param_specs=param_specs, quant=quant)


# ---------------------------------------------------------------------------
# Public factory
# ---------------------------------------------------------------------------

def make_mixer(spec: MixingSpec | TopologySchedule, cfg: MixerConfig,
               mesh=None, client_axes: Sequence[str] = ("clients",),
               param_specs: Pytree | None = None,
               placement=None) -> Callable:
    """Return mixer(x_stacked, z_stacked, key=None, t=None) -> x_next.

    Semantics (both backends, matching the paper):
      unquantized (Alg. 1, eq. 5):  x' = W @ z
      quantized   (Alg. 2, eq. 7):  x' = x + W @ Q(z - x)

    A :class:`TopologySchedule` instead of a static spec returns the
    time-varying mixer(x, z, key, t) -> (x', active) — see
    :func:`make_scheduled_mixer`. Every mixer accepts the round counter
    ``t`` (static impls ignore it), so ``make_round_step`` passes it
    uniformly.

    ``placement`` (a ``gossip_plan.Placement`` from
    :func:`~repro.core.gossip_plan.compute_placement`, sparse impls
    only): run the compiled plan placed — lanes carry relabeled clients
    so boundary wire follows the partition cut instead of the contiguous
    split. Callers hold client state in LANE order (gather inputs
    through ``placement.perm`` once at build; see ``make_round_step``).
    """
    if isinstance(spec, TopologySchedule):
        return make_scheduled_mixer(spec, cfg, mesh=mesh,
                                    client_axes=client_axes,
                                    param_specs=param_specs,
                                    placement=placement)
    impl = cfg.resolved_impl(spec, mesh, client_axes)
    quant = cfg.quant
    if placement is not None and impl not in ("ring", "torus", "sparse"):
        raise ValueError(
            f"placement requires a sparse backend, got impl={impl!r}")

    if impl == "ring" and spec.kind == "torus":
        impl = "torus"  # historical alias: ring impl on a torus spec

    if impl in ("ring", "torus", "sparse"):
        if _clients_per_shard(mesh, client_axes, spec.m) is None:
            if placement is not None:
                raise ValueError(
                    "placement needs a usable client mesh (the dense "
                    f"fallback has no lanes to place): m={spec.m}, "
                    f"client_axes={tuple(client_axes)!r}")
            if impl == "torus" and quant is not None and quant.enabled:
                # Explicitly requested quantized torus without a usable
                # mesh: fall back to the dense reference — LOUDLY (this
                # used to happen silently).
                warnings.warn(
                    "quantized torus mixer without a usable client mesh "
                    "falls back to the DENSE reference path (all-gather "
                    "traffic, not 4 ppermutes); pass a mesh whose client "
                    "axes multiply to a divisor of m (a client block per "
                    "shard) for the sparse backend",
                    UserWarning, stacklevel=2)

                def mixer(x, z, key=None, t=None):
                    return _mix_dense_quantized(spec.W, x, z, quant, key)
                return mixer
            raise ValueError(
                f"mixer impl {impl!r} needs a mesh with one client block "
                f"per shard (m={spec.m}, "
                f"client_axes={tuple(client_axes)!r})")
        if impl != "sparse" and spec.kind != impl:
            raise ValueError(f"{impl} mixer needs a {impl} MixingSpec, "
                             f"got kind={spec.kind!r}")
        plan = spec.gossip_plan()
        if placement is not None:
            plan = plan.placed(placement)
        return make_plan_mixer(plan, mesh, client_axes,
                               param_specs=param_specs, quant=quant,
                               wire=cfg.wire)

    if impl == "dense":
        if quant is None or not quant.enabled:
            def mixer(x, z, key=None, t=None):
                del x, key, t
                return mix_dense(spec.W, z)
            return mixer

        def mixer(x, z, key=None, t=None):
            del t
            return _mix_dense_quantized(spec.W, x, z, quant, key)
        return mixer

    raise ValueError(f"unknown mixer impl {impl!r}")


def consensus_distance(stacked: Pytree) -> jnp.ndarray:
    """(1/m) sum_i ||x(i) - xbar||^2 — Lemma 4's left-hand side, a useful
    training-time diagnostic of how far clients have drifted apart."""
    def per_leaf(z):
        zb = jnp.mean(z, axis=0, keepdims=True)
        return jnp.sum((z.astype(jnp.float32) - zb) ** 2) / z.shape[0]

    return jax.tree.reduce(jnp.add, jax.tree.map(per_leaf, stacked))
