"""What decides ``correct``: the program's first rounds, as the timed step
ran them, against the plain float32 reference of the same rounds.

Readings of a run (program or reference), after the first
:data:`CHECK_ROUNDS` rounds from the same weights on the same batches:

* ``losses`` [3]: each round's loss, the mean over clients and local
  steps;
* ``n1``, ``n3`` [m, leaves]: the norm of each client's change of each
  parameter leaf from the start, after round 1 and after round 3;
* ``spread`` [leaves]: the norm of the clients' spread about their mean
  after round 3;
* reference only, ``g0`` [leaves]: the norm of client 0's first
  gradient, which decides the leaves that count.

Numbers compared, each against its own limit:

* ``loss1``, ``loss2``, ``loss3``: the gap of each round's loss, in
  nats (round 1's starts from equal weights, so it is free of the
  wire's noise);
* ``change1``, ``change3``: the worst leaf's gap between the program's
  change norm and the reference's, over the reference's norm of that
  leaf or of the median leaf, whichever is larger;
* ``spread3``: the same for the clients' spread (the gossip makes it).

The reference computes in float32 at ``HIGHEST`` precision and keeps its
parameters and momentum in the dtype the configuration states, as the
program does. Its gossip is the paper's quantized recursion in its
``lemma5`` form, ``x'_i = sum_j W_ij (x_j + Q(z_j - x_j))``, with the
paper's unbiased stochastic quantizer: per client and leaf the step
``s = max|z - x| / (2^(b-1) - 1)``, each value rounded to a neighbouring
multiple of ``s`` with the probability of its distance, by draws of the
reference's own. The program's draws differ from these, so the numbers
compare norms, which the wire's noise moves alike on both sides.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from references.ops import Matmul

# A leaf counts only if its first gradient in the reference is at least
# this share of the median leaf's: below it, a leaf moves by round-off.
LEAF_FLOOR = 1e-3
FAULTS = ("half_batch", "no_exchange")
CHECK_ROUNDS = 3


def ring_matrix(m: int, self_weight: float) -> np.ndarray:
    """Mixing matrix of a ring: ``self_weight`` on the diagonal, the rest
    split between the two neighbours (one neighbour, twice, at m = 2)."""
    W = np.zeros((m, m), np.float64)
    for i in range(m):
        W[i, i] += self_weight
        W[i, (i + 1) % m] += (1 - self_weight) / 2
        W[i, (i - 1) % m] += (1 - self_weight) / 2
    return W


TOPOLOGIES = {"ring": ring_matrix}


def mixing_matrix(work: dict) -> np.ndarray:
    """The mixing matrix of a cell's ``topology``; exit with an error on
    one the harness cannot build (the program's static round mixes over
    a ring)."""
    build = TOPOLOGIES.get(work["topology"])
    if build is None:
        raise SystemExit(f"chipbench: topology {work['topology']!r} is not "
                         f"one the harness builds ({sorted(TOPOLOGIES)})")
    return build(work["clients"], work["self_weight"])


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                      for a in jax.tree.leaves(tree)])


@functools.partial(jax.jit, static_argnames=("init", "cfg_items"))
def _readings_of(params, key, *, init, cfg_items):
    """Per-client change norms [m, leaves] of stacked ``params`` from the
    weights ``init`` makes from ``key``, and the spread [leaves]."""
    x0 = init(key, dict(cfg_items))
    norms, spread = [], []
    for p, a in zip(jax.tree.leaves(params), jax.tree.leaves(x0)):
        p = p.astype(jnp.float32)
        dl = p - a.astype(jnp.float32)[None]
        norms.append(jnp.sqrt(jnp.sum(jnp.square(dl).reshape(
            p.shape[0], -1), axis=1)))
        dev = p - jnp.mean(p, axis=0, keepdims=True)
        spread.append(jnp.sqrt(jnp.sum(jnp.square(dev))))
    return jnp.stack(norms, axis=1), jnp.stack(spread)


def change_readings(params, key, ref, cfg: dict):
    """(n [m, leaves], spread [leaves]) as numpy, of stacked params."""
    n, s = _readings_of(params, key, init=ref.init,
                        cfg_items=tuple(sorted(cfg.items())))
    return np.asarray(n, np.float64), np.asarray(s, np.float64)


def make_client_round(ref, cfg: dict, *, eta: float, theta: float,
                      mm: Matmul, rows: int | None = None):
    """One client's K heavy-ball steps (momentum restarts each round),
    the gradient of each step taken row by row so that it fits beside
    the rest. ``rows`` uses only the first rows of each minibatch (a
    planted fault). Returns jitted ``(x, tokens [K,b,l], targets) ->
    (z, mean loss, first-gradient leaf norms)``."""
    row_loss = lambda p, t, y: ref.loss(p, t, y, cfg, mm)
    vg = jax.value_and_grad(row_loss)

    def grad_batch(yf, tokens, targets):
        if rows is not None:
            tokens, targets = tokens[:rows], targets[:rows]

        def body(acc, row):
            l, g = vg(yf, *row)
            return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

        zero = (jnp.zeros((), jnp.float32),
                jax.tree.map(jnp.zeros_like, yf))
        (l, g), _ = jax.lax.scan(body, zero, (tokens, targets))
        n = tokens.shape[0]
        return l / n, jax.tree.map(lambda a: a / n, g)

    def step(carry, batch):
        y, v = carry
        loss, g = grad_batch(_f32(y), *batch)
        v = jax.tree.map(lambda vl, gl: (theta * vl.astype(jnp.float32)
                                         - eta * gl).astype(vl.dtype), v, g)
        y = jax.tree.map(lambda yl, vl: (yl.astype(jnp.float32)
                                         + vl.astype(jnp.float32)
                                         ).astype(yl.dtype), y, v)
        return (y, v), (loss, _leaf_norms(g))

    @jax.jit
    def client_round(x, tokens, targets):
        v0 = jax.tree.map(jnp.zeros_like, x)
        (z, _), (losses, gn) = jax.lax.scan(step, (x, v0),
                                            (tokens, targets))
        return z, jnp.mean(losses), gn[0]

    return client_round


@functools.partial(jax.jit, static_argnames=("bits",))
def _sent(x, z, key, *, bits: int):
    """One client's ``x + Q(z - x)`` per leaf, in float32: the paper's
    stochastic b-bit quantizer with a step of its own for each leaf
    (``bits`` 32 sends ``z`` as it is)."""
    out = []
    for i, (xl, zl) in enumerate(zip(jax.tree.leaves(x),
                                     jax.tree.leaves(z))):
        xf, d = xl.astype(jnp.float32), (zl.astype(jnp.float32)
                                         - xl.astype(jnp.float32))
        if bits < 32:
            qmax = 2 ** (bits - 1) - 1
            s = jnp.max(jnp.abs(d)) / qmax
            s = jnp.where(s > 0, s, 1.0)
            a = d / s
            k = jnp.floor(a)
            u = jax.random.uniform(jax.random.fold_in(key, i), d.shape)
            k = jnp.clip(k + (u < a - k), -qmax - 1, qmax)
            d = k * s
        out.append(xf + d)
    return jax.tree.unflatten(jax.tree.structure(x), out)


@jax.jit
def _mix(W, ys, like):
    """x'_i = sum_j W_ij y_j in float32 over the clients' trees ``ys``,
    stacked and stored in the dtype of ``like``'s leaves."""
    m = len(ys)

    def leaf(b, *y):
        return jnp.stack([sum(W[i, j] * y[j] for j in range(m))
                          for i in range(m)]).astype(b.dtype)
    return jax.tree.map(leaf, like, *ys)


def reference_readings(ref, cfg: dict, work: dict, key, batches_of, *,
                       q_key, fp8: bool = False,
                       fault: str | None = None) -> dict:
    """Run the reference over the first :data:`CHECK_ROUNDS` rounds and
    return its readings. ``batches_of(r)`` gives round r's batches
    ``{"tokens", "targets"}`` [m, K, b, l]; ``q_key`` seeds the wire's
    draws. ``fp8`` computes it as the control; ``fault`` plants one of
    :data:`FAULTS`."""
    m = work["clients"]
    W = mixing_matrix(work)
    if fault == "no_exchange":
        W = np.eye(m)
    W = jnp.asarray(W, jnp.float32)
    rows = work["batch"] // 2 if fault == "half_batch" else None
    client_round = make_client_round(ref, cfg, eta=work["eta"],
                                     theta=work["theta"], mm=Matmul(fp8),
                                     rows=rows)
    x0 = ref.init(key, cfg)
    x = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (m,) + a.shape),
                     x0)
    del x0
    out = {"losses": []}
    for r in range(CHECK_ROUNDS):
        b = batches_of(r)
        zs, losses = [], []
        for c in range(m):
            xc = jax.tree.map(lambda a: a[c], x)
            z, loss, g0 = client_round(xc, b["tokens"][c],
                                       b["targets"][c])
            zs.append(_sent(xc, z, jax.random.fold_in(q_key, r * m + c),
                            bits=work["bits"]))
            del z
            losses.append(float(loss))
            if r == 0 and c == 0:
                out["g0"] = np.asarray(g0, np.float64)
        x = _mix(W, zs, x)
        del zs
        out["losses"].append(float(np.mean(losses)))
        if r == 0:
            out["n1"], _ = change_readings(x, key, ref, cfg)
    out["n3"], out["spread"] = change_readings(x, key, ref, cfg)
    out["losses"] = np.asarray(out["losses"])
    return out


def _norm_gap(p, r, keep):
    """Worst gap |p - r| over max(r, median r) across kept leaves; p, r
    [..., leaves]."""
    med = np.median(r[..., keep], axis=-1, keepdims=True)
    gap = np.abs(p - r) / np.maximum(r, med)
    return float(np.max(gap[..., keep]))


def numbers(prog: dict, ref: dict) -> dict:
    """The numbers compared, from two sets of readings."""
    g0 = ref["g0"]
    keep = g0 >= LEAF_FLOOR * np.median(g0)
    gaps = np.abs(prog["losses"] - ref["losses"])
    out = {"loss1": float(gaps[0]), "loss2": float(gaps[1]),
           "loss3": float(gaps[2])}
    out.update(change1=_norm_gap(prog["n1"], ref["n1"], keep),
               change3=_norm_gap(prog["n3"], ref["n3"], keep),
               spread3=_spread_gap(prog, ref, keep))
    return out


def _spread_gap(prog, ref, keep):
    """The clients' spread gap, over the reference's mean change norm of
    that leaf or of the median leaf (the spread itself may be 0)."""
    scale = ref["n3"].mean(axis=0)
    med = np.median(scale[keep])
    gap = np.abs(prog["spread"] - ref["spread"]) / np.maximum(scale, med)
    return float(np.max(gap[keep]))


def verdict(nums: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and the checks to print: each number beside its limit.
    A number whose limit is null is shown and not compared."""
    checks, ok = {}, True
    for name, value in nums.items():
        lim = limits.get(name)
        checks[name] = {"value": value, "limit": lim}
        if lim is not None and not (np.isfinite(value) and value <= lim):
            ok = False
    return ok, checks
