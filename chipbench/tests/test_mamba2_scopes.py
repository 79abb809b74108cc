"""The Mamba2 mixer's scopes on a compiled round step (CPU), and what the
``ssd_ms`` and ``ssm_mixer_ms`` readers find there: forward and backward
ops under ``mamba/``, and nothing else. The scopes change op metadata
only: with every ``jax.named_scope`` made a no-op, the compiled program
of either architecture, stripped of its metadata, is the same."""
import contextlib
import dataclasses
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import spec
import trace_reduce as tr
from repro.configs import get_config, reduced
from repro.core import (DFedAvgMConfig, MixingSpec, QuantConfig, RoundState,
                        make_round_step)
from repro.models import model as M

M_CLIENTS = 2
MAMBA = re.compile(r"(?:^|[/(])mamba/")


@pytest.fixture(scope="module")
def no_compile_cache():
    # A cached executable keeps the metadata of the program that
    # compiled it.
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", saved)


def _compiled_text(arch: str, scoped: bool) -> str:
    """HLO text of a tiny DFedAvgM round step of ``arch`` (one layer, 2
    clients on one device, K=2, q8 sparse ring)."""
    cfg = dataclasses.replace(reduced(get_config(arch)), n_layers=1)
    mesh = Mesh(np.array(jax.devices()[:1]), ("clients",))
    dfed = DFedAvgMConfig(eta=0.03, theta=0.9, local_steps=2,
                          quant=QuantConfig(bits=8), mixer_impl="sparse")
    loss = lambda p, b, r: M.loss_fn(p, cfg, b, r)
    step = make_round_step(loss, dfed, MixingSpec.ring(M_CLIENTS, 0.5),
                           mesh=mesh, client_axes=("clients",))
    clients = NamedSharding(mesh, P("clients"))
    rep = NamedSharding(mesh, P())
    shapes = jax.eval_shape(lambda k: M.init_model(k, cfg)[0],
                            jax.random.PRNGKey(0))
    state = RoundState(
        params=jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            (M_CLIENTS,) + s.shape, s.dtype, sharding=clients), shapes),
        rng=jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep),
        round=jax.ShapeDtypeStruct((), jnp.int32, sharding=rep))
    batches = {k: jax.ShapeDtypeStruct((M_CLIENTS, 2, 2, 16), jnp.int32,
                                       sharding=rep)
               for k in ("tokens", "targets")}
    no_scope = mock.patch.object(jax, "named_scope",
                                 lambda name: contextlib.nullcontext())
    with contextlib.nullcontext() if scoped else no_scope:
        return jax.jit(step).lower(state, batches).compile().as_text()


def _stripped(hlo: str) -> str:
    """The program without its debug info: no metadata, no stack-frame
    tables."""
    hlo = re.sub(r",? metadata=\{[^}]*\}", "", hlo)
    return re.split(r"^(?:FileNames|FunctionNames|FileLocations|"
                    r"StackFrames)$", hlo, maxsplit=1, flags=re.M)[0]


@pytest.fixture(scope="module")
def mamba_hlo(no_compile_cache):
    return _compiled_text("mamba2-780m", scoped=True)


def _read(metric: str, scopes: list) -> float | None:
    """The reader of ``metric`` on a trace with one op of 1 us for each
    scope in ``scopes``, one after the other, over one round."""
    ops = [tr.Op(i * 1000, (i + 1) * 1000, f"op.{i}", s)
           for i, s in enumerate(scopes)]
    red = tr.Reduced(chips=[ops], spans=[("bench/wait", 0, len(ops) * 1000)],
                     lo=0, hi=len(ops) * 1000)
    cell = spec.Cell(spec.load(), "mamba2.k4.q8")
    return cell.reader(metric)(red, {"rounds": 1})


def test_readers_find_forward_and_backward_mixer_ops(mamba_hlo):
    scopes = list(tr.hlo_scopes(mamba_hlo).values())
    ssd = re.compile(r"(?:^|[/(])mamba/ssd(?:[/)]|$)")
    mixer = [s for s in scopes if MAMBA.search(s)]
    ssd_ops = [s for s in scopes if ssd.search(s)]
    for found in (mixer, ssd_ops):
        assert any("transpose(" in s for s in found)        # backward
        assert any("transpose(" not in s and "/sgd/grad/" in s
                   for s in found)                          # forward
    assert {"proj", "conv", "ssd", "gate"} == {
        m.group(1) for s in mixer
        for m in re.finditer(r"mamba/(\w+)", s)}
    # Each reader counts exactly its ops: 1 us each, one round.
    assert _read("ssd_ms", scopes) == pytest.approx(1e-3 * len(ssd_ops))
    assert _read("ssm_mixer_ms", scopes) == pytest.approx(
        1e-3 * len(mixer))


def test_readers_match_the_scope_in_its_transformed_forms():
    scopes = ["jit(f)/round/local_sgd/sgd/grad/jvp(mamba/ssd)/dot",
              "jit(f)/round/local_sgd/sgd/grad/transpose(jvp(mamba/ssd))/dot",
              "jit(f)/sgd/grad/jvp()/while/body/closed_call/mamba/ssd/exp",
              "jit(f)/sgd/grad/jvp(mamba/conv)/add",
              "jit(f)/sgd/grad/transpose(jvp(mamba/gate))/dot",
              "jit(f)/round/local_sgd/sgd/grad/jvp()/dot",
              "jit(f)/round/mix/wire/encode/pallas_call",
              "jit(f)/notmamba/ssd/add"]
    assert _read("ssd_ms", scopes) == pytest.approx(3e-3)
    assert _read("ssm_mixer_ms", scopes) == pytest.approx(5e-3)
    assert _read("ssd_ms", scopes[5:]) is None
    assert _read("ssm_mixer_ms", scopes[5:]) is None


@pytest.mark.parametrize("arch", ["olmo-1b", "mamba2-780m"])
def test_scopes_change_metadata_only(no_compile_cache, mamba_hlo, arch):
    scoped = (mamba_hlo if arch == "mamba2-780m"
              else _compiled_text(arch, scoped=True))
    bare = _compiled_text(arch, scoped=False)
    assert (MAMBA.search(scoped) is not None) == (arch == "mamba2-780m")
    assert MAMBA.search(bare) is None and "round/local_sgd" not in bare
    assert "round/local_sgd" in scoped
    assert _stripped(scoped) == _stripped(bare)
