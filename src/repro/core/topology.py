"""Communication graphs and mixing matrices (paper §2, Definition 1).

A mixing matrix ``W`` for a connected undirected graph ``G=(V,E)`` must
satisfy (Definition 1):

  1. (Graph)      w_ij = 0 iff i != j and (i,j) not in E, else w_ij > 0
  2. (Symmetry)   W = W^T
  3. (Null space) null(I - W) = span(1)
  4. (Spectral)   I >= W > -I

The key scalar is ``lambda(W) = max(|lambda_2|, |lambda_m|)`` — the
second-largest eigenvalue magnitude — which controls the gossip mixing
speed (Lemma 1: ||W^k - 11^T/m||_op <= lambda^k).
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Sequence

import numpy as np

__all__ = [
    "Graph",
    "ring_graph",
    "chain_graph",
    "torus_graph",
    "complete_graph",
    "star_graph",
    "erdos_renyi_graph",
    "metropolis_hastings",
    "max_degree_weights",
    "lazy_uniform",
    "spectral_gap",
    "mixing_lambda",
    "check_mixing_matrix",
    "MixingSpec",
    "TopologySchedule",
    "metropolis_weights_from_adjacency",
]


@dataclasses.dataclass(frozen=True)
class Graph:
    """Undirected graph on m nodes stored as a boolean adjacency matrix.

    ``adj`` excludes self-loops; every mixing-matrix constructor adds the
    diagonal itself.
    """

    adj: np.ndarray  # [m, m] bool, symmetric, zero diagonal
    name: str = "custom"

    def __post_init__(self):
        a = np.asarray(self.adj, dtype=bool)
        if a.ndim != 2 or a.shape[0] != a.shape[1]:
            raise ValueError(f"adjacency must be square, got {a.shape}")
        if not np.array_equal(a, a.T):
            raise ValueError("adjacency must be symmetric (undirected graph)")
        if a.diagonal().any():
            raise ValueError("adjacency must have zero diagonal")
        object.__setattr__(self, "adj", a)

    @property
    def m(self) -> int:
        return self.adj.shape[0]

    def degrees(self) -> np.ndarray:
        return self.adj.sum(axis=1)

    def neighbors(self, i: int) -> np.ndarray:
        return np.nonzero(self.adj[i])[0]

    def edges(self) -> Iterable[tuple[int, int]]:
        ii, jj = np.nonzero(np.triu(self.adj, k=1))
        return list(zip(ii.tolist(), jj.tolist()))

    def num_directed_edges(self) -> int:
        """sum_i deg(i) — what the paper's comm-cost formulas count."""
        return int(self.adj.sum())

    def block_boundary_edges(self, clients_per_shard: int,
                             perm=None) -> int:
        """Directed edges that CROSS a contiguous client-block boundary
        when client ``c`` lives on shard ``c // clients_per_shard`` — the
        only edges the block-sharded sparse backend ships over the wire
        (intra-block edges are on-device lane gathers). For a ring this
        is ``2 * n_shards`` regardless of ``m``: the O(n_shards *
        boundary_degree) scaling that lets ``m`` grow past the device
        count.

        ``perm`` bills a PLACED layout instead: a lane->client
        permutation (or a ``gossip_plan.Placement``, whose ``.perm`` is
        used) under which client ``perm[p]`` occupies lane ``p``, i.e.
        shard ``p // clients_per_shard`` — the cut ``--placement
        partition`` actually ships."""
        if clients_per_shard < 1 or self.m % clients_per_shard:
            raise ValueError(f"clients_per_shard={clients_per_shard} "
                             f"must divide m={self.m}")
        if perm is None:
            shard = np.arange(self.m) // clients_per_shard
        else:
            p = np.asarray(getattr(perm, "perm", perm), dtype=np.int64)
            if not np.array_equal(np.sort(p), np.arange(self.m)):
                raise ValueError("perm must be a permutation of "
                                 f"range({self.m})")
            shard = np.empty(self.m, dtype=np.int64)
            shard[p] = np.arange(self.m) // clients_per_shard
        return int((self.adj & (shard[:, None] != shard[None, :])).sum())

    def is_connected(self) -> bool:
        m = self.m
        seen = np.zeros(m, dtype=bool)
        stack = [0]
        seen[0] = True
        while stack:
            u = stack.pop()
            for v in np.nonzero(self.adj[u])[0]:
                if not seen[v]:
                    seen[v] = True
                    stack.append(int(v))
        return bool(seen.all())


def ring_graph(m: int) -> Graph:
    """The paper's experimental topology: a simple ring (§6)."""
    if m < 2:
        raise ValueError("ring needs m >= 2")
    adj = np.zeros((m, m), dtype=bool)
    for i in range(m):
        adj[i, (i + 1) % m] = True
        adj[(i + 1) % m, i] = True
    if m == 2:  # the two "edges" coincide
        adj = np.array([[False, True], [True, False]])
    return Graph(adj, name=f"ring{m}")


def chain_graph(m: int) -> Graph:
    """Path 0-1-...-m-1: the worst-diameter connected topology."""
    adj = np.zeros((m, m), dtype=bool)
    for i in range(m - 1):
        adj[i, i + 1] = adj[i + 1, i] = True
    return Graph(adj, name=f"chain{m}")


def torus_graph(rows: int, cols: int) -> Graph:
    """2-D torus — the natural match for a TPU 2-D mesh with wraparound."""
    m = rows * cols
    adj = np.zeros((m, m), dtype=bool)

    def idx(r, c):
        return (r % rows) * cols + (c % cols)

    for r in range(rows):
        for c in range(cols):
            u = idx(r, c)
            for v in (idx(r + 1, c), idx(r, c + 1)):
                if u != v:
                    adj[u, v] = adj[v, u] = True
    return Graph(adj, name=f"torus{rows}x{cols}")


def complete_graph(m: int) -> Graph:
    """All-to-all: gossip degenerates to exact averaging each round."""
    adj = ~np.eye(m, dtype=bool)
    return Graph(adj, name=f"complete{m}")


def star_graph(m: int) -> Graph:
    """Node 0 is the hub — the *centralized* FedAvg topology as a graph."""
    adj = np.zeros((m, m), dtype=bool)
    adj[0, 1:] = True
    adj[1:, 0] = True
    return Graph(adj, name=f"star{m}")


def erdos_renyi_graph(m: int, p: float, seed: int = 0) -> Graph:
    """Random G(m,p), resampled until connected (bounded retries)."""
    rng = np.random.default_rng(seed)
    for _ in range(256):
        u = rng.random((m, m))
        adj = np.triu(u < p, k=1)
        adj = adj | adj.T
        g = Graph(adj, name=f"er{m}_p{p}")
        if g.is_connected():
            return g
    raise RuntimeError(f"could not sample a connected G({m},{p})")


# ---------------------------------------------------------------------------
# Mixing matrices
# ---------------------------------------------------------------------------

def metropolis_hastings(graph: Graph) -> np.ndarray:
    """Metropolis–Hastings weights [Boyd et al. 2004], cited in the paper.

    w_ij = 1 / (1 + max(deg_i, deg_j)) for (i,j) in E; diagonal fills the
    slack. Always satisfies Definition 1 for a connected graph.
    """
    deg = graph.degrees()
    m = graph.m
    W = np.zeros((m, m), dtype=np.float64)
    for i, j in graph.edges():
        w = 1.0 / (1.0 + max(deg[i], deg[j]))
        W[i, j] = W[j, i] = w
    np.fill_diagonal(W, 1.0 - W.sum(axis=1))
    return W


def max_degree_weights(graph: Graph) -> np.ndarray:
    """Maximum-degree weights: w_ij = 1/(1+deg_max) on edges."""
    dmax = int(graph.degrees().max())
    m = graph.m
    W = np.where(graph.adj, 1.0 / (dmax + 1.0), 0.0)
    np.fill_diagonal(W, 1.0 - W.sum(axis=1))
    return W


def lazy_uniform(graph: Graph, self_weight: float = 1.0 / 3.0) -> np.ndarray:
    """Uniform neighbor weights with a fixed self-weight.

    For a ring with self_weight=1/3 this is the classic (1/3,1/3,1/3)
    gossip matrix used in the paper's experiments.
    """
    deg = graph.degrees().astype(np.float64)
    if (deg == 0).any():
        raise ValueError("graph has isolated nodes")
    m = graph.m
    W = np.where(graph.adj, ((1.0 - self_weight) / deg)[:, None], 0.0)
    # Symmetrize: only valid uniformly if the graph is regular.
    if not np.allclose(W, W.T):
        raise ValueError("lazy_uniform requires a regular graph; "
                         "use metropolis_hastings instead")
    np.fill_diagonal(W, self_weight)
    return W


def mixing_lambda(W: np.ndarray) -> float:
    """lambda(W) = max(|lambda_2|, |lambda_m|) (paper §2)."""
    ev = np.sort(np.linalg.eigvalsh(np.asarray(W, dtype=np.float64)))[::-1]
    return float(max(abs(ev[1]), abs(ev[-1])))


def spectral_gap(W: np.ndarray) -> float:
    """1 - lambda(W): appears in the denominators of Thm 1 / Lemma 4."""
    return 1.0 - mixing_lambda(W)


def check_mixing_matrix(W: np.ndarray, graph: Graph | None = None,
                        atol: float = 1e-10) -> None:
    """Raise if W violates Definition 1. Used by tests and constructors.

    ``atol`` is the tolerance of every equality test (symmetry, row sums,
    the eigenvalue-1 tests); row sums and eigenvalues are never held
    tighter than 1e-8. Pass ~1e-6 for a W built in float32."""
    W = np.asarray(W, dtype=np.float64)
    tol = max(atol, 1e-8)
    m = W.shape[0]
    if W.shape != (m, m):
        raise ValueError("W must be square")
    if not np.allclose(W, W.T, atol=atol):
        raise ValueError("W must be symmetric")
    if not np.allclose(W.sum(axis=1), 1.0, atol=tol):
        raise ValueError("rows of W must sum to 1")
    ev = np.linalg.eigvalsh(W)
    if ev.min() <= -1.0 + 1e-12:
        raise ValueError("need W > -I (smallest eigenvalue > -1)")
    if ev.max() > 1.0 + tol:
        raise ValueError("need I >= W")
    # null(I - W) = span(1)  <=>  eigenvalue 1 is simple (for connected G).
    if np.sum(np.abs(ev - 1.0) < tol) != 1:
        raise ValueError("eigenvalue 1 of W must be simple "
                         "(is the graph connected?)")
    if graph is not None:
        off = ~np.eye(m, dtype=bool)
        if np.any((W != 0) & off & ~graph.adj):
            raise ValueError("W has weight on a non-edge")
        if np.any((np.abs(W) < atol) & graph.adj):
            raise ValueError("W must be strictly positive on edges")


@dataclasses.dataclass(frozen=True)
class MixingSpec:
    """A graph + mixing matrix bundle consumed by core.mixing.

    ``kind`` records whether the sparse ring path (ppermute) may be used.
    """

    graph: Graph
    W: np.ndarray
    kind: str  # "ring" | "torus" | "dense"
    torus_shape: tuple[int, int] | None = None

    @property
    def m(self) -> int:
        return self.graph.m

    @property
    def lam(self) -> float:
        return mixing_lambda(self.W)

    @staticmethod
    def ring(m: int, self_weight: float = 1.0 / 3.0) -> "MixingSpec":
        g = ring_graph(m)
        if m == 2:
            W = np.array([[self_weight, 1 - self_weight],
                          [1 - self_weight, self_weight]])
        else:
            W = lazy_uniform(g, self_weight=self_weight)
        check_mixing_matrix(W, g)
        return MixingSpec(graph=g, W=W, kind="ring")

    @staticmethod
    def dense(graph: Graph, scheme: str = "metropolis") -> "MixingSpec":
        if scheme == "metropolis":
            W = metropolis_hastings(graph)
        elif scheme == "max_degree":
            W = max_degree_weights(graph)
        else:
            raise ValueError(f"unknown scheme {scheme!r}")
        check_mixing_matrix(W, graph)
        return MixingSpec(graph=graph, W=W, kind="dense")

    @staticmethod
    def complete(m: int) -> "MixingSpec":
        """W = 11^T/m — makes DFedAvgM coincide with (all-client) FedAvg."""
        g = complete_graph(m)
        W = np.full((m, m), 1.0 / m)
        check_mixing_matrix(W, g)
        return MixingSpec(graph=g, W=W, kind="dense")

    def gossip_plan(self):
        """Compile this static spec into a :class:`~repro.core.gossip_plan.
        GossipPlan` with baked weights — the IR both mixer backends
        consume (ring/torus lower to their shift decompositions, any other
        graph to matchings)."""
        from .gossip_plan import plan_from_spec
        return plan_from_spec(self)

    @staticmethod
    def torus(rows: int, cols: int,
              self_weight: float = 0.2) -> "MixingSpec":
        """2-D torus with uniform neighbor weights — the natural gossip
        graph for a physical 2-D TPU mesh (4 ppermutes instead of an
        all-gather; much smaller lambda than a ring of the same size).
        kind="torus" enables the sparse shard_map mixer."""
        g = torus_graph(rows, cols)
        deg = g.degrees()
        if not (deg == deg[0]).all():
            raise ValueError("torus must be regular")
        w_nb = (1.0 - self_weight) / float(deg[0])
        W = np.where(g.adj, w_nb, 0.0)
        np.fill_diagonal(W, self_weight)
        check_mixing_matrix(W, g)
        return MixingSpec(graph=g, W=W, kind="torus",
                          torus_shape=(rows, cols))


# ---------------------------------------------------------------------------
# Time-varying topologies: a round-indexed schedule of mixing events
# ---------------------------------------------------------------------------

def metropolis_weights_from_adjacency(adj):
    """Metropolis–Hastings reweighting of a (possibly traced) 0/1 adjacency.

    ``adj`` is an [m, m] float array — symmetric, zero diagonal — that may be
    a jax tracer, so a per-round sampled subgraph can be reweighted *inside*
    the jitted round step. For any such adjacency (connected or not) the
    result is symmetric and doubly stochastic with eigenvalues in [-1, 1];
    rows of isolated nodes degenerate to e_i (the client holds its value).
    """
    import jax.numpy as jnp

    a = jnp.asarray(adj, dtype=jnp.float32)
    deg = a.sum(axis=1)
    pair = 1.0 + jnp.maximum(deg[:, None], deg[None, :])
    W = a / pair
    return W + jnp.diag(1.0 - W.sum(axis=1))


@dataclasses.dataclass(frozen=True)
class TopologySchedule:
    """A round-indexed sequence of mixing events ``(W_t, active_t)``.

    Generalizes a static :class:`MixingSpec` to *time-varying* gossip: each
    communication round ``t`` draws a doubly-stochastic ``W_t`` (and a mask
    of participating clients) from a PRNG key, entirely in-graph so the
    whole training loop stays jittable. Inactive clients hold their
    parameters and send nothing: their ``W_t`` rows degenerate to ``e_i``
    and the mixer gates their freshly-trained ``z`` back to ``x``.

    Kinds:
      * ``constant``     — ``W_t = W`` every round; reproduces the static
                           mixer bit-for-bit (the trivial schedule).
      * ``edge_sample``  — each base-graph edge is kept i.i.d. with prob
                           ``p_edge`` per round; the surviving subgraph is
                           Metropolis-reweighted (FedPAQ-style intermittent
                           links).
      * ``partial``      — each client participates i.i.d. with prob
                           ``p_active``; only edges between two active
                           clients carry messages. With ``exact=True``
                           EXACTLY ``n_active = round(p_active * m)``
                           clients are drawn per round (FedAvg-style fixed
                           cohorts) — the static count lets the round step
                           skip inactive clients' local-SGD compute
                           entirely (see ``static_active_count``). With
                           ``cap_slack=c`` the i.i.d. draw is CAPPED at
                           ``n_cap = ceil(p_active * m) + c`` participants
                           (overflow rounds — the binomial upper tail,
                           rare for slack of a few sd — clamp a uniformly
                           random subset of the extras, so no client is
                           systematically favored) — a static upper bound
                           that buys the same compute skip via a padded
                           gather.
      * ``random_walk``  — a single gossip token walks the base graph; round
                           ``t`` pairwise-averages the token's current and
                           next node (random-walk DFedAvg, arXiv:2508.21286
                           flavor). By default the walk path is precomputed
                           host-side from ``seed`` (data-independent), so
                           per-round lookup is O(1) in-graph. With
                           ``stateful=True`` there is NO precomputed path:
                           the token position is *training-loop state*
                           (threaded through ``RoundState.token``) and each
                           round samples the next neighbor in-graph — the
                           walk can run forever and react to runtime
                           signals.
      * ``cycle``        — deterministic cycle over a list of mixing
                           matrices (e.g. alternating ring/torus).

    All kinds guarantee every sampled ``W_t`` is symmetric, doubly
    stochastic, and zero off the active edge set (tests enforce this).
    """

    kind: str                      # constant|edge_sample|partial|random_walk|cycle
    m: int
    name: str = "schedule"
    base_W: np.ndarray | None = None      # constant
    adj: np.ndarray | None = None         # edge_sample / partial / random_walk
    p_edge: float = 1.0                   # edge_sample
    p_active: float = 1.0                 # partial
    n_active: int | None = None           # partial(exact=True): cohort size
    n_cap: int | None = None              # partial(cap_slack=...): iid cap
    walk: np.ndarray | None = None        # random_walk: [horizon+1] int32 path
                                          #   (None = stateful in-graph token)
    start: int = 0                        # random_walk(stateful): initial token
    Ws: np.ndarray | None = None          # cycle: [n, m, m] stacked matrices

    _KINDS = ("constant", "edge_sample", "partial", "random_walk", "cycle")

    def __post_init__(self):
        if self.kind not in self._KINDS:
            raise ValueError(f"unknown schedule kind {self.kind!r}")

    # -- properties the mixer / ledger dispatch on ------------------------

    @property
    def is_stochastic(self) -> bool:
        """Whether sampling round t's event consumes PRNG randomness."""
        return self.kind in ("edge_sample", "partial") or self.is_stateful

    @property
    def is_stateful(self) -> bool:
        """Whether the schedule carries in-graph state across rounds (the
        random-walk token position, threaded through ``RoundState.token``
        by ``make_round_step``). Stateful schedules sample via
        :meth:`token_event`, not :meth:`sample_w`."""
        return self.kind == "random_walk" and self.walk is None

    @property
    def gates_participation(self) -> bool:
        """Whether some clients may sit a round out (mixer must gate z)."""
        return self.kind in ("partial", "random_walk")

    @property
    def static_active_count(self) -> int | None:
        """Static UPPER BOUND on the participating clients per round, or
        None when no bound below m is known. Exact for cohorts
        (``partial(exact=True)``) and random walks (2); the configured cap
        for capped i.i.d. participation. A static bound (< m) lets the
        round step gather just the active lanes, run local SGD on a
        [k, ...] stack, and scatter back — actually SKIPPING inactive
        clients' compute instead of gating it out after the fact (padded
        slots for the capped case)."""
        if self.kind == "random_walk":
            return 2
        if self.kind == "partial" and self.n_active is not None:
            return self.n_active
        if self.kind == "partial" and self.n_cap is not None:
            return self.n_cap
        return None

    def expected_directed_edges(self, t: int | None = None) -> float:
        """E[#directed edges carrying a message in round t] — the quantity
        per-round communication cost is proportional to. For deterministic
        kinds with ``t`` given, the count is exact for that round."""
        if self.kind == "constant":
            return float(np.count_nonzero(
                self.base_W - np.diag(np.diag(self.base_W))))
        if self.kind == "cycle":
            counts = [float(np.count_nonzero(W - np.diag(np.diag(W))))
                      for W in self.Ws]
            if t is not None:
                return counts[int(t) % len(counts)]
            return float(np.mean(counts))
        base = float(self.adj.sum())
        if self.kind == "edge_sample":
            return self.p_edge * base
        if self.kind == "partial":
            if self.n_active is not None:
                # exact cohorts: edge live iff both endpoints drawn into
                # the size-k cohort (without replacement)
                k, m = self.n_active, self.m
                return k * (k - 1) / (m * (m - 1)) * base
            # an edge is live iff both endpoints drew active (with a
            # participation cap this slightly overcounts the clamped
            # binomial upper tail — negligible for slack of a few sd)
            return self.p_active ** 2 * base
        return 2.0  # random_walk: one undirected edge per round

    # -- in-graph sampling ------------------------------------------------

    def sample_w(self, key, t):
        """(key, round) -> (W_t [m,m] f32, active [m] f32). Jit-safe."""
        import jax
        import jax.numpy as jnp

        m = self.m
        ones = jnp.ones((m,), jnp.float32)
        if self.kind == "constant":
            return jnp.asarray(self.base_W, jnp.float32), ones
        if self.kind == "cycle":
            Ws = jnp.asarray(self.Ws, jnp.float32)
            t = jnp.asarray(t, jnp.int32)
            return Ws[t % Ws.shape[0]], ones
        if self.kind == "edge_sample":
            adj = jnp.asarray(self.adj, jnp.float32)
            u = jnp.triu(jax.random.uniform(key, (m, m)), k=1)
            u = u + u.T   # one uniform per undirected edge, symmetric
            keep = (u < self.p_edge).astype(jnp.float32) * adj
            return metropolis_weights_from_adjacency(keep), ones
        if self.kind == "partial":
            adj = jnp.asarray(self.adj, jnp.float32)
            if self.n_active is not None:
                cohort = jax.random.permutation(key, m)[: self.n_active]
                active = (jnp.zeros((m,), jnp.float32)
                          .at[cohort].set(1.0))
            else:
                active = (jax.random.uniform(key, (m,))
                          < self.p_active).astype(jnp.float32)
                if self.n_cap is not None and self.n_cap < m:
                    # Cap the draw at the static bound the padded compute
                    # gather is sized for. Overflow rounds (the binomial
                    # upper tail) clamp a KEY-DERIVED RANDOM subset of
                    # the extras — clamping by client index would
                    # systematically underweight high-indexed clients'
                    # data whenever the cap binds.
                    perm = jax.random.permutation(
                        jax.random.fold_in(key, 1), m)
                    keep_perm = jnp.cumsum(active[perm]) <= self.n_cap
                    keep = (jnp.zeros((m,), jnp.float32)
                            .at[perm].set(keep_perm.astype(jnp.float32)))
                    active = active * keep
            live = adj * active[:, None] * active[None, :]
            return metropolis_weights_from_adjacency(live), active
        # random_walk: token edge (pos[t], pos[t+1]) pairwise-averages
        if self.is_stateful:
            raise ValueError(
                "stateful random_walk has no precomputed path: its token "
                "position is training-loop state — sample via token_event "
                "(make_round_step threads RoundState.token automatically)")
        t = jnp.asarray(t, jnp.int32)
        pos = jnp.asarray(self.walk, jnp.int32)
        horizon = pos.shape[0] - 1
        i = pos[t % horizon]
        j = pos[t % horizon + 1]
        return self._token_pair_event(i, j)

    def _token_pair_event(self, i, j):
        """W_t and active mask for a pairwise average across edge (i, j)."""
        import jax.numpy as jnp

        m = self.m
        W = (jnp.eye(m, dtype=jnp.float32)
             .at[i, i].add(-0.5).at[j, j].add(-0.5)
             .at[i, j].add(0.5).at[j, i].add(0.5))
        active = jnp.zeros((m,), jnp.float32).at[i].set(1.0).at[j].set(1.0)
        return W, active

    # -- stateful (token-carrying) sampling --------------------------------

    def init_token(self):
        """Initial in-graph walk state for a stateful random walk."""
        import jax.numpy as jnp

        if not self.is_stateful:
            raise ValueError(f"schedule {self.name!r} carries no token")
        return jnp.asarray(self.start, jnp.int32)

    def sample_w_token(self, key, token):
        """(key, token) -> (W_t, active, token_next): one in-graph step of
        the walk. The next position is drawn uniformly from the current
        node's neighbors (the same chain the host-side precomputed path
        samples — but as jittable training-loop state)."""
        import jax
        import jax.numpy as jnp

        adj = jnp.asarray(self.adj, jnp.float32)
        row = adj[token]
        nxt = jax.random.choice(key, self.m, p=row / row.sum())
        W, active = self._token_pair_event(token, nxt)
        return W, active, jnp.asarray(nxt, jnp.int32)

    def support_graph(self) -> Graph:
        """The union of every edge ANY round of this schedule can sample —
        the static support the sparse backend compiles its ppermute plan
        against (per-round W_t then masks the unsampled edges to 0)."""
        if self.kind == "constant":
            adj = (self.base_W - np.diag(np.diag(self.base_W))) != 0
        elif self.kind == "cycle":
            adj = np.zeros((self.m, self.m), dtype=bool)
            for W in self.Ws:
                adj |= (W - np.diag(np.diag(W))) != 0
        else:
            adj = np.asarray(self.adj) != 0
        return Graph(adj, name=f"support[{self.name}]")

    def gossip_plan(self):
        """Structure-only :class:`~repro.core.gossip_plan.GossipPlan` over
        :meth:`support_graph`; weights are gathered from each round's
        sampled ``W_t`` (see ``GossipPlan.gather_weights``)."""
        from .gossip_plan import plan_from_support
        return plan_from_support(self.support_graph(), name=self.name)

    def gossip_plans(self) -> list:
        """Dynamic per-round plans. For a ``cycle`` this compiles one
        *static* plan per member matrix (its own support, baked weights),
        so a round only moves its member's wire edges instead of masking
        the whole union support — the sparse backend ``lax.switch``es
        between them on ``t mod n``. Every other kind returns the single
        union-support plan ``[self.gossip_plan()]``."""
        if self.kind != "cycle":
            return [self.gossip_plan()]
        from .gossip_plan import plan_from_matrix
        return [plan_from_matrix(W, name=f"{self.name}[{k}]")
                for k, W in enumerate(self.Ws)]

    def _split_mix_key(self, key_mix):
        import jax

        if self.is_stochastic:
            return jax.random.split(key_mix)
        return key_mix, key_mix

    def round_event(self, key_mix, t):
        """Derive round t's (W_t, active, key_quant) from the round-step's
        mixing key — the single source of truth for how the key is split,
        shared by the mixer, tests, and benchmarks."""
        key_topo, key_q = self._split_mix_key(key_mix)
        W, active = self.sample_w(key_topo, t)
        return W, active, key_q

    def token_event(self, key_mix, token):
        """Stateful analogue of :meth:`round_event`: derive the round's
        (W_t, active, key_quant, token_next) from the mixing key and the
        carried token position."""
        key_topo, key_q = self._split_mix_key(key_mix)
        W, active, token_next = self.sample_w_token(key_topo, token)
        return W, active, key_q, token_next

    # -- constructors -----------------------------------------------------

    @staticmethod
    def constant(spec: MixingSpec) -> "TopologySchedule":
        """The trivial schedule: static W every round (bit-identical to the
        dense static mixer on the same key)."""
        return TopologySchedule(kind="constant", m=spec.m,
                                name=f"constant[{spec.graph.name}]",
                                base_W=np.asarray(spec.W, np.float64))

    @staticmethod
    def edge_sample(graph: Graph, p_edge: float) -> "TopologySchedule":
        if not 0.0 < p_edge <= 1.0:
            raise ValueError("need 0 < p_edge <= 1")
        return TopologySchedule(kind="edge_sample", m=graph.m,
                                name=f"edge_sample[{graph.name},p={p_edge}]",
                                adj=graph.adj.astype(np.float64),
                                p_edge=float(p_edge))

    @staticmethod
    def partial(graph: Graph, p_active: float, exact: bool = False,
                cap_slack: int | None = None) -> "TopologySchedule":
        """``exact=False``: each client participates i.i.d. w.p.
        ``p_active``. ``exact=True``: exactly ``round(p_active * m)``
        clients are drawn (without replacement) every round — a FedAvg-
        style fixed cohort whose statically known size lets the round step
        skip inactive clients' local-SGD compute. ``cap_slack`` (i.i.d.
        mode only): cap the draw at ``ceil(p_active * m) + cap_slack``
        participants — a static upper bound that buys the same compute
        skip through a padded gather; rounds whose binomial draw overflows
        the cap (rare for slack of a few standard deviations) clamp a
        key-derived uniformly random subset of the extras to inactive, so
        the clamp introduces no per-client bias."""
        if not 0.0 < p_active <= 1.0:
            raise ValueError("need 0 < p_active <= 1")
        n_active = n_cap = None
        tag = f"p={p_active}"
        if exact:
            if cap_slack is not None:
                raise ValueError("cap_slack applies to i.i.d. partial "
                                 "participation; exact cohorts already "
                                 "have a static count")
            n_active = max(1, round(p_active * graph.m))
            tag = f"k={n_active}"
        elif cap_slack is not None:
            if cap_slack < 0:
                raise ValueError("need cap_slack >= 0")
            n_cap = min(graph.m,
                        int(np.ceil(p_active * graph.m)) + int(cap_slack))
            tag = f"p={p_active},cap={n_cap}"
        return TopologySchedule(kind="partial", m=graph.m,
                                name=f"partial[{graph.name},{tag}]",
                                adj=graph.adj.astype(np.float64),
                                p_active=float(p_active), n_active=n_active,
                                n_cap=n_cap)

    @staticmethod
    def random_walk(graph: Graph, horizon: int = 4096, seed: int = 0,
                    start: int = 0, stateful: bool = False
                    ) -> "TopologySchedule":
        """``stateful=False``: precompute a ``horizon``-step walk on
        ``graph``; round t gossips across walk edge (pos[t], pos[t+1]),
        wrapping modulo horizon. ``stateful=True``: no precomputed path —
        the token position lives in ``RoundState.token`` and each round
        samples the next neighbor in-graph (never wraps, jit-safe,
        reactive to runtime state)."""
        if not graph.is_connected():
            raise ValueError("random walk needs a connected base graph")
        if stateful:
            return TopologySchedule(
                kind="random_walk", m=graph.m,
                name=f"random_walk[{graph.name},stateful]",
                adj=graph.adj.astype(np.float64), start=int(start))
        rng = np.random.default_rng(seed)
        pos = np.empty(horizon + 1, dtype=np.int32)
        pos[0] = start
        for k in range(horizon):
            pos[k + 1] = rng.choice(graph.neighbors(int(pos[k])))
        return TopologySchedule(kind="random_walk", m=graph.m,
                                name=f"random_walk[{graph.name}]",
                                adj=graph.adj.astype(np.float64), walk=pos)

    @staticmethod
    def cycle(specs: Sequence[MixingSpec]) -> "TopologySchedule":
        """Deterministic cycle W_t = specs[t mod n].W (e.g. ring/torus
        alternation). All specs must share m."""
        if not specs:
            raise ValueError("cycle needs at least one MixingSpec")
        m = specs[0].m
        if any(s.m != m for s in specs):
            raise ValueError("all specs in a cycle must have the same m")
        Ws = np.stack([np.asarray(s.W, np.float64) for s in specs])
        names = "/".join(s.graph.name for s in specs)
        return TopologySchedule(kind="cycle", m=m, name=f"cycle[{names}]",
                                Ws=Ws)
