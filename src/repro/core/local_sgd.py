"""Local training: K steps of SGD with heavy-ball momentum (paper eq. 4).

  y^{t,k+1}(i) = y^{t,k}(i) - eta * g~^{t,k}(i) + theta * (y^{t,k}(i) - y^{t,k-1}(i))

with y^{t,-1} = y^{t,0} = x^t(i) — i.e. the momentum buffer RESTARTS at the
beginning of every communication round. Equivalent velocity form used here:

  v_0 = 0;  v_{k+1} = theta * v_k - eta * g_k;  y_{k+1} = y_k + v_{k+1}

The whole K-step loop is a single ``lax.scan`` so XLA sees one fused step
body regardless of K. A fused Pallas kernel for the elementwise update is
available in ``repro.kernels.momentum_sgd`` and can be switched in via
``use_fused_kernel=True`` (interpret-mode on CPU).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

Pytree = Any
LossFn = Callable[..., jnp.ndarray]  # (params, batch, rng) -> scalar

__all__ = ["local_train", "local_train_deferred", "heavy_ball_update"]


def heavy_ball_update(y: Pytree, v: Pytree, g: Pytree, eta: float,
                      theta: float, fused_fn=None) -> tuple[Pytree, Pytree]:
    """One heavy-ball step on a pytree. Returns (y_next, v_next)."""
    if fused_fn is not None:
        return fused_fn(y, v, g, eta, theta)

    # The trailing cast is a no-op for python-float eta/theta (weak-typed
    # arithmetic already lands in vl.dtype) but keeps the buffer dtype
    # when eta is a TRACED f32 scalar (the async engine's staleness-
    # adaptive per-client learning rate) and vl is lower precision.
    v_next = jax.tree.map(
        lambda vl, gl: (theta * vl - eta * gl.astype(vl.dtype))
        .astype(vl.dtype), v, g)
    y_next = jax.tree.map(jnp.add, y, v_next)
    return y_next, v_next


def local_train(loss_fn: LossFn, params: Pytree, batches: Pytree,
                key: jax.Array, *, eta: float, theta: float,
                fused_update=None) -> tuple[Pytree, jnp.ndarray]:
    """Run K heavy-ball SGD steps on one client.

    Args:
      loss_fn: (params, batch, rng) -> scalar loss.
      params:  this client's parameters x^t(i) (pytree).
      batches: pytree whose leaves have leading axis K — one minibatch per
               local step (K is inferred, static under jit).
      key:     client PRNG key (consumed for per-step rng + stochasticity).
      eta, theta: learning rate and momentum of eq. (4).
      fused_update: optional fused elementwise update (Pallas kernel wrapper).

    Returns:
      (y^{t,K}, mean local loss over the K steps).
    """
    K = jax.tree.leaves(batches)[0].shape[0]
    v0 = jax.tree.map(jnp.zeros_like, params)
    grad_fn = jax.value_and_grad(loss_fn)

    def body(carry, inp):
        y, v = carry
        batch, k = inp
        with jax.named_scope("sgd/grad"):
            loss, g = grad_fn(y, batch, k)
        with jax.named_scope("sgd/update"):
            y, v = heavy_ball_update(y, v, g, eta, theta,
                                     fused_fn=fused_update)
        return (y, v), loss

    keys = jax.random.split(key, K)
    (y_K, _), losses = jax.lax.scan(body, (params, v0), (batches, keys))
    return y_K, jnp.mean(losses)


def local_train_deferred(loss_fn: LossFn, params: Pytree, batches: Pytree,
                         key: jax.Array, *, eta: float, theta: float,
                         fused_update=None
                         ) -> tuple[Pytree, Pytree, Pytree, jnp.ndarray]:
    """Fused-round variant of :func:`local_train`: stop BEFORE applying the
    (K-1)th update, returning the raw material of the last two steps so the
    round step can fuse them into the wire encode/decode kernels:

      * scan applies steps ``0 .. K-3`` exactly as :func:`local_train`
        (same per-step keys — ``jax.random.split(key, K)`` — same batches);
      * step ``K-2``'s loss and gradient are computed but the update is NOT
        applied (the fused encoder folds ``v' = theta*v - eta*g;
        y' = y + v'`` into the quantize+pack pass);
      * step ``K-1``'s gradient is computed later by the caller, inside the
        gossip overlap window, and folded into the decode-apply kernel.

    Requires K >= 2. Returns ``(y_{K-2}, v_{K-2}, g_{K-1}, losses)`` with
    ``losses`` the STACKED [K-1] per-step losses of steps ``0 .. K-2`` (the
    caller appends the last step's and takes the mean, keeping loss parity
    with the unfused round).
    """
    K = jax.tree.leaves(batches)[0].shape[0]
    if K < 2:
        raise ValueError(f"deferred local training needs K >= 2, got {K}")
    v0 = jax.tree.map(jnp.zeros_like, params)
    grad_fn = jax.value_and_grad(loss_fn)

    def body(carry, inp):
        y, v = carry
        batch, k = inp
        with jax.named_scope("sgd/grad"):
            loss, g = grad_fn(y, batch, k)
        with jax.named_scope("sgd/update"):
            y, v = heavy_ball_update(y, v, g, eta, theta,
                                     fused_fn=fused_update)
        return (y, v), loss

    keys = jax.random.split(key, K)
    head = jax.tree.map(lambda b: b[:K - 2], batches)
    (y, v), losses = jax.lax.scan(body, (params, v0), (head, keys[:K - 2]))
    batch_pen = jax.tree.map(lambda b: b[K - 2], batches)
    with jax.named_scope("sgd/grad"):
        loss_pen, g = grad_fn(y, batch_pen, keys[K - 2])
    losses = jnp.concatenate([losses, loss_pen[None]])
    return y, v, g, losses
