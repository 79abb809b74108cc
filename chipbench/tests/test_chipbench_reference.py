"""The plain references against the program's models at a small size
(CPU, float32 at HIGHEST precision): the same weights give the same
loss and, for OLMo, the same gradient. The references import nothing of
the program; the test does, to compare."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from references import mamba2, olmo
from references.ops import Matmul
from repro.configs import get_config
from repro.models import model as M

SMALL = {
    "olmo-1b": (olmo, dict(d_model=64, n_heads=4, n_kv_heads=4,
                           head_dim=16, d_ff=128, vocab_size=256,
                           n_layers=2)),
    "mamba2-780m": (mamba2, dict(d_model=64, ssm_state=16, ssm_head_dim=16,
                                 vocab_size=256, n_layers=2)),
}
KEYS = ("d_model", "n_heads", "head_dim", "d_ff", "vocab_size", "n_layers",
        "ssm_state", "ssm_expand", "ssm_head_dim", "rope_theta", "dtype")


def _setup(arch, seq):
    mod, over = SMALL[arch]
    pc = dataclasses.replace(get_config(arch), dtype="float32", remat=False,
                             **over)
    cfg = {k: getattr(pc, k) for k in KEYS}
    params = mod.init(jax.random.PRNGKey(1), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(2), (3, seq), 0, 256)
    return mod, pc, cfg, params, tok, jnp.roll(tok, -1, axis=1)


@pytest.mark.parametrize("arch", sorted(SMALL))
def test_init_has_the_program_layout(arch):
    mod, pc, cfg, params, _, _ = _setup(arch, 8)
    theirs = jax.eval_shape(lambda k: M.init_model(k, pc)[0],
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(params) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


@pytest.mark.parametrize("arch", sorted(SMALL))
def test_forward_loss_matches_program(arch):
    # seq 100 < one SSD chunk: the program's Mamba2 backward is NaN once
    # a chunk is full (see PERF.md), its forward is fine at any length.
    mod, pc, cfg, params, tok, tgt = _setup(arch, 100)
    with jax.default_matmul_precision("highest"):
        want = float(M.loss_fn(params, pc, {"tokens": tok,
                                            "targets": tgt}))
    loss = jax.jit(jax.vmap(lambda p, a, b: mod.loss(p, a, b, cfg, Matmul()),
                            in_axes=(None, 0, 0)))
    got = float(jnp.mean(loss(params, tok, tgt)))
    # The program's norms use epsilon 1e-6, the published models 1e-5.
    assert got == pytest.approx(want, rel=1e-5)


def test_olmo_gradient_matches_program():
    mod, pc, cfg, params, tok, tgt = _setup("olmo-1b", 64)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: M.loss_fn(p, pc, {"tokens": tok,
                                                    "targets": tgt}))(params)
    got = jax.jit(jax.grad(lambda p: jnp.mean(jax.vmap(
        lambda a, b: mod.loss(p, a, b, cfg, Matmul()))(tok, tgt))))(params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-2 * float(
            jnp.max(jnp.abs(b)))


def test_mamba2_reference_gradient_is_finite_on_full_chunks():
    mod, pc, cfg, params, tok, tgt = _setup("mamba2-780m", 256)
    g = jax.jit(jax.grad(lambda p: mod.loss(p, tok[0], tgt[0], cfg,
                                            Matmul())))(params)
    assert all(bool(jnp.isfinite(a).all()) for a in jax.tree.leaves(g))


def test_fp8_control_rounds_its_operands():
    a = jnp.linspace(-3.0, 3.0, 64).reshape(8, 8)
    exact = Matmul()("ij,jk->ik", a, a)
    low = Matmul(fp8=True)("ij,jk->ik", a, a)
    gap = float(jnp.max(jnp.abs(low - exact)) / jnp.max(jnp.abs(exact)))
    assert 1e-3 < gap < 0.1
