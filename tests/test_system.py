"""End-to-end behaviour tests for the system (replaces the scaffold
placeholder): full training runs reproducing the paper's qualitative
claims at small scale, plus the serving path."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import (DFedAvgMConfig, FedAvgConfig, MixingSpec,
                        QuantConfig, average_params, init_round_state,
                        make_fedavg_step, make_round_step)
from repro.data import FederatedDataset, classification_dataset
from repro.models.paper_nets import apply_2nn, init_2nn, softmax_xent

M, K, B = 8, 4, 32


def _acc(params, data):
    pred = jnp.argmax(apply_2nn(params, jnp.asarray(data.x)), -1)
    return float((pred == jnp.asarray(data.y)).mean())


def _run(step, fed, rounds, seed=0):
    p0 = init_2nn(jax.random.PRNGKey(seed))
    st = init_round_state(jax.tree.map(
        lambda t: jnp.broadcast_to(t[None], (M,) + t.shape), p0),
        jax.random.PRNGKey(seed + 1))
    step = jax.jit(step)
    for t in range(rounds):
        st, mt = step(st, fed.round_batches(t, K=K, batch=B))
    return st, mt


def loss_fn(p, batch, rng):
    return softmax_xent(apply_2nn(p, batch["x"]), batch["y"])


@pytest.fixture(scope="module")
def data():
    return classification_dataset(n=4000, d=784, seed=0)


def test_dfedavgm_trains_iid(data):
    fed = FederatedDataset.make(data, M, iid=True)
    step = make_round_step(loss_fn, DFedAvgMConfig(
        eta=0.05, theta=0.9, local_steps=K), MixingSpec.ring(M))
    st, _ = _run(step, fed, 40)
    assert _acc(average_params(st.params), data) > 0.9


def test_quantized_matches_unquantized_iid(data):
    """Paper Figs 2-5: communication bits do not affect performance."""
    fed = FederatedDataset.make(data, M, iid=True)
    accs = {}
    for bits in (32, 8):
        q = QuantConfig(bits=bits) if bits < 32 else None
        step = make_round_step(loss_fn, DFedAvgMConfig(
            eta=0.05, theta=0.9, local_steps=K, quant=q),
            MixingSpec.ring(M, self_weight=0.5))
        st, _ = _run(step, fed, 40)
        accs[bits] = _acc(average_params(st.params), data)
    assert accs[8] > accs[32] - 0.03, accs


def test_noniid_gap(data):
    """Paper §6.1: FedAvg reaches high accuracy on Non-IID; DFedAvgM (ring)
    lags — neighbors don't cover all classes."""
    res = {}
    for iid in (True, False):
        fed = FederatedDataset.make(data, M, iid=iid)
        d_step = make_round_step(loss_fn, DFedAvgMConfig(
            eta=0.05, theta=0.9, local_steps=K), MixingSpec.ring(M))
        f_step = make_fedavg_step(loss_fn, FedAvgConfig(
            eta=0.05, theta=0.9, local_steps=K), M)
        std, _ = _run(d_step, fed, 40)
        stf, _ = _run(f_step, fed, 40)
        res[iid] = (_acc(average_params(std.params), data),
                    _acc(average_params(stf.params), data))
    d_iid, f_iid = res[True]
    d_non, f_non = res[False]
    assert f_non - d_non > (f_iid - d_iid)       # the non-IID gap grows
    assert f_non > 0.9


def test_serve_pipeline_runs():
    from repro.launch.serve import greedy_generate
    from repro.models import init_model
    cfg = dataclasses.replace(reduced(get_config("smollm-135m")),
                              remat=False)
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                 cfg.vocab_size)
    toks = greedy_generate(params, cfg, prompts, gen=6, s_alloc=20)
    assert toks.shape == (2, 6)
    assert int(toks.max()) < cfg.vocab_size


def test_train_driver_cli():
    from repro.launch.train import main as train_main
    res = train_main([
        "--arch", "smollm-135m", "--rounds", "4", "--clients", "4",
        "--batch", "2", "--seq", "32", "--bits", "8"])
    assert bool(jnp.isfinite(res.metrics["loss"]))
    # rounds <= 10 log every round, each with its host time
    assert [r["t"] for r in res.rounds] == [0, 1, 2, 3]
    assert all(r["round_s"] > 0 for r in res.rounds)


def test_train_driver_layers_cut_keeps_widths():
    """``--layers`` cuts only the depth: the stacked layer axis shrinks,
    every width stays the (reduced) config's."""
    from repro.launch.train import main as train_main
    res = train_main(["--arch", "olmo-1b", "--layers", "1", "--rounds", "1",
                      "--clients", "2", "--local-steps", "1",
                      "--batch", "1", "--seq", "8"])
    cfg = reduced(get_config("olmo-1b"))
    assert res.state.params["embed"]["table"].shape == (2, cfg.vocab_size,
                                                        cfg.d_model)
    for leaf in jax.tree.leaves(res.state.params["stages"]):
        assert leaf.shape[:2] == (2, 1), leaf.shape


def test_train_driver_traces_step_once_on_mesh():
    """Client state placed on a one-device client mesh goes in as the step
    gives it back (size-1 mesh axes dropped), so every round hits the one
    entry of jit's dispatch cache."""
    from repro.launch.train import main as train_main
    res = train_main(["--arch", "olmo-1b", "--layers", "1", "--rounds", "3",
                      "--clients", "2", "--clients-per-shard", "2",
                      "--mixer-impl", "sparse", "--bits", "8",
                      "--local-steps", "1", "--batch", "1", "--seq", "8"])
    assert res.step._cache_size() == 1


@pytest.mark.parametrize("from_env", (True, False))
def test_compile_cache_dir(tmp_path, from_env):
    """A set JAX_COMPILATION_CACHE_DIR receives the compiles and is left
    as JAX's own setting; unset, the cache goes to <repo>/.jax_cache."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.pathsep.join(sys.path))
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import REPO_CACHE_DIR, use_compile_cache
where = use_compile_cache()
assert jax.config.jax_compilation_cache_dir == where, where
if "{tmp}" == "-":
    assert where == str(REPO_CACHE_DIR), where
else:
    assert where == "{tmp}", where
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()
print("CACHE_OK")
""".format(tmp=tmp_path if from_env else "-")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert "CACHE_OK" in r.stdout, r.stdout + r.stderr
    if from_env:
        assert any(tmp_path.iterdir()), "no compile landed in the cache"


def test_compile_cache_key_holds_scopes(tmp_path):
    """Two programs that differ only in a ``jax.named_scope`` get cache
    entries of their own (a loaded executable names its ops by the scopes
    of the program that compiled it), the same program hits its entry
    from a fresh process, and source paths enter the key relative to
    the repository root."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.pathsep.join(sys.path))
    code = """
import os, re, sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import REPO_ROOT, use_compile_cache
use_compile_cache()
pat = jax.config.jax_hlo_source_file_canonicalization_regex
assert re.sub(pat, "", os.path.join(REPO_ROOT, "src", "m.py")) == \\
    os.path.join("src", "m.py")
def f(x):
    with jax.named_scope(sys.argv[1]):
        return x * 3 + 1
jax.jit(f)(jnp.arange(8.0)).block_until_ready()
print("ENTRIES", len(os.listdir(jax.config.jax_compilation_cache_dir)))
"""

    def entries(scope):
        r = subprocess.run([sys.executable, "-c", code, scope],
                           capture_output=True, text=True, timeout=300,
                           env=env)
        out = [l for l in r.stdout.splitlines() if l.startswith("ENTRIES")]
        assert out, r.stdout + r.stderr
        return int(out[0].split()[1])

    first = entries("round/mix")
    assert first > 0
    assert entries("round/mix") == first
    assert entries("round/metrics") > first


def test_chip_path_never_imports_dryrun():
    """``launch/dryrun.py`` rewrites XLA_FLAGS when imported; the chip
    path (the training entry point and ``chip_smoke.py``) must not load
    it."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import sys, chip_smoke, repro.launch.train; "
            "assert 'repro.launch.dryrun' not in sys.modules; print('OK')")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=repo,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": os.pathsep.join([os.path.join(repo, "src"),
                                            repo])})
    assert r.stdout.strip() == "OK", r.stdout + r.stderr
