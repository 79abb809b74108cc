"""The one traffic generator: a round's token batches from a workload's
parameters and the run's seed.

Each client takes ``local_steps`` minibatches of ``batch`` sequences of
``seq`` tokens per round. A sequence follows ``t_{j+1} = t_j + 5 (mod
vocab)`` from a start drawn per row, so every row of every round
differs, and the stream is learnable. Copied from the program's
``data/synthetic.lm_round_batches`` so that a later change there cannot
move the benchmark's inputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

STRIDE = 5


def seed_key(seed: int):
    """A PRNG key for any whole-number seed, including seeds wider than
    32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31),
                              seed // 2**31)


@functools.partial(jax.jit, static_argnames=("m", "K", "batch", "seq",
                                             "vocab"))
def round_batches(key, round_idx, *, m: int, K: int, batch: int, seq: int,
                  vocab: int) -> dict:
    """``{"tokens", "targets"}``, each int32 [m, K, batch, seq], for
    round ``round_idx`` (a traced int, so every round reuses one
    compiled program)."""
    k = jax.random.fold_in(key, round_idx)
    start = jax.random.randint(k, (m, K, batch, 1), 0, vocab)
    ar = jnp.arange(seq + 1, dtype=jnp.int32)
    tokens = (start + STRIDE * ar) % vocab
    return {"tokens": tokens[..., :seq].astype(jnp.int32),
            "targets": tokens[..., 1:].astype(jnp.int32)}
