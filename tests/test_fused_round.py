"""The fused round (``DFedAvgMConfig.fuse_round``): variant semantics,
backend parity, and kernel-level structure.

The fused round is an algorithm VARIANT — it defers the last local step
past the mix (neighbors see y_{K-1}, not y_K), trading one step of wire
freshness for a single-pass tail and wire/compute overlap. The contract
pinned here:

  * at ``eta == 0`` the deferred updates vanish and the fused round is
    BITWISE equal to the default round (fp32 AND stochastic q8 — the
    quantization PRNG discipline is shared);
  * the fused sparse (GossipPlan / block-sharded) backend matches the
    fused dense reference to ~ulp for every quant mode, gating included;
  * config validation: needs K >= 2, no stateful schedules, no
    skip_inactive_compute=True;
  * STRUCTURE (jaxpr, on the ``wire="planar"`` build): the local scan
    runs K-2 steps, the tail is exactly ONE fused encode kernel
    (momentum+quantize+pack) plus ONE fused decode kernel
    (dequant+mix+momentum), and no standalone momentum / plain codec
    kernel survives anywhere in the round.

Mesh-backed cases run in a subprocess with 8 forced host devices (same
harness as test_sparse_backend_mesh).
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DFedAvgMConfig, MixingSpec, QuantConfig,
                        TopologySchedule, init_round_state, make_round_step)
from repro.core.topology import ring_graph

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

M, D = 8, 33


def run_sub(code: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") +
        f" --xla_force_host_platform_device_count={devices}").strip()
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    # A CPU run with fake host devices: never the parent's accelerator.
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def _loss(p, b, r):
    return 0.5 * jnp.sum((p["w"] - b["c"]) ** 2) \
        + 0.1 * jnp.sum(p["u"] ** 2)


def _problem(m=M, K=3, seed=0):
    kp, kb = jax.random.split(jax.random.PRNGKey(seed))
    params = {"w": jax.random.normal(kp, (m, D)),
              "u": jax.random.normal(jax.random.fold_in(kp, 1), (m, 3, 7))}
    batches = {"c": jax.random.normal(kb, (m, K, D))}
    return params, batches


def _run(cfg, spec, rounds=3, K=3, seed=0):
    params, batches = _problem(K=K, seed=seed)
    step = jax.jit(make_round_step(_loss, cfg, spec))
    st = init_round_state(params, jax.random.PRNGKey(7))
    for _ in range(rounds):
        st, mt = step(st, batches)
    return st, mt


QUANTS = [None,
          QuantConfig(bits=8, stochastic=False, delta_mode="lemma5"),
          QuantConfig(bits=8, stochastic=True, delta_mode="eq7")]


@pytest.mark.parametrize("quant", QUANTS,
                         ids=["fp32", "q8-lemma5", "q8-eq7-stoch"])
def test_fused_eta0_bitwise_equal_to_unfused(quant):
    """At eta == 0 the deferred updates are zero, so fused == unfused bit
    for bit — including the stochastic-rounding draws (shared PRNG
    discipline)."""
    spec = MixingSpec.ring(M, self_weight=0.5)
    base = DFedAvgMConfig(eta=0.0, theta=0.9, local_steps=3, quant=quant,
                          mixer_impl="dense")
    st_u, mt_u = _run(base, spec)
    st_f, mt_f = _run(dataclasses.replace(base, fuse_round=True), spec)
    for a, b in zip(jax.tree.leaves(st_u.params),
                    jax.tree.leaves(st_f.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the loss METRIC averages the same per-step values but reduces them
    # in a differently-fused graph — ~ulp, params stay bitwise
    np.testing.assert_allclose(float(mt_u["loss"]), float(mt_f["loss"]),
                               rtol=1e-6)


def test_fused_changes_trajectory_at_nonzero_eta():
    """The variant really is a variant: with eta > 0 the deferred step
    changes the trajectory (if it didn't, the fusion would be a no-op)."""
    spec = MixingSpec.ring(M, self_weight=0.5)
    base = DFedAvgMConfig(eta=0.05, theta=0.9, local_steps=3,
                          mixer_impl="dense")
    st_u, _ = _run(base, spec)
    st_f, _ = _run(dataclasses.replace(base, fuse_round=True), spec)
    assert np.isfinite(np.asarray(st_f.params["w"])).all()
    assert not np.array_equal(np.asarray(st_u.params["w"]),
                              np.asarray(st_f.params["w"]))


def test_fuse_round_config_validation():
    spec = MixingSpec.ring(M, self_weight=0.5)
    with pytest.raises(ValueError, match="local_steps >= 2"):
        make_round_step(_loss, DFedAvgMConfig(local_steps=1,
                                              fuse_round=True), spec)
    walk = TopologySchedule.random_walk(ring_graph(M), stateful=True)
    with pytest.raises(ValueError, match="stateful"):
        make_round_step(_loss, DFedAvgMConfig(local_steps=3,
                                              fuse_round=True), walk)
    with pytest.raises(ValueError, match="skip_inactive_compute"):
        make_round_step(_loss, DFedAvgMConfig(local_steps=3,
                                              fuse_round=True), spec,
                        skip_inactive_compute=True)


_SUB_PRELUDE = """
    import dataclasses
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import (DFedAvgMConfig, MixingSpec, QuantConfig,
                            TopologySchedule, init_round_state,
                            make_round_step)
    from repro.core.topology import ring_graph

    D = 33

    def loss(p, b, r):
        return 0.5 * jnp.sum((p["w"] - b["c"]) ** 2) \\
            + 0.1 * jnp.sum(p["u"] ** 2)

    def problem(m, K, seed=0):
        kp, kb = jax.random.split(jax.random.PRNGKey(seed))
        params = {"w": jax.random.normal(kp, (m, D)),
                  "u": jax.random.normal(jax.random.fold_in(kp, 1),
                                         (m, 3, 7))}
        batches = {"c": jax.random.normal(kb, (m, K, D))}
        return params, batches

    def run(cfg, spec, m, K, rounds=3, **kw):
        params, batches = problem(m, K)
        step = jax.jit(make_round_step(loss, cfg, spec, **kw))
        st = init_round_state(params, jax.random.PRNGKey(7))
        for _ in range(rounds):
            st, mt = step(st, batches)
        return st, mt

    def leafmax(a, b):
        return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                         - y.astype(jnp.float32))))
                   for x, y in zip(jax.tree.leaves(a.params),
                                   jax.tree.leaves(b.params)))
"""


def test_fused_sparse_matches_dense_on_mesh():
    """Fused sparse (masked-ppermute GossipPlan backend) == fused dense
    reference for fp32 and every quant mode, static ring AND a scheduled
    partial cohort (inactive-client gating). fp32 parity is ~ulp;
    deterministic quantization sits on a floor knife-edge (the two
    backends reduce the amax scale in different orders, so a delta
    landing within an ulp of an integer multiple of s can floor apart),
    bounding parity at ONE quantizer step — s = amax/127 ≲ 2e-3 at this
    problem's delta magnitudes. Real backend bugs (wrong weights, lost
    replica, broken gating) show up at O(1e-1)."""
    run_sub(_SUB_PRELUDE + """
    M = 8
    mesh = Mesh(np.array(jax.devices()[:M]), ("clients",))
    quants = [None,
              QuantConfig(bits=8, stochastic=False, delta_mode="lemma5"),
              QuantConfig(bits=8, stochastic=False, delta_mode="eq7"),
              QuantConfig(bits=8, stochastic=True, delta_mode="lemma5")]
    specs = [MixingSpec.ring(M, self_weight=0.5),
             TopologySchedule.partial(ring_graph(M), 0.5)]
    for spec in specs:
        for q in quants:
            cfg = DFedAvgMConfig(eta=0.05, theta=0.9, local_steps=3,
                                 quant=q, fuse_round=True)
            st_d, _ = run(dataclasses.replace(cfg, mixer_impl="dense"),
                          spec, M, 3)
            st_s, mt = run(dataclasses.replace(cfg, mixer_impl="sparse"),
                           spec, M, 3, mesh=mesh,
                           client_axes=("clients",))
            diff = leafmax(st_d, st_s)
            tol = 1e-6 if q is None else 2.5e-3   # one quantizer step
            assert diff <= tol, (spec, q, diff)
    print("OK")
    """)


def test_fused_block_sharded_matches_dense():
    """Block sharding (m=32 clients over 8 shards, m_local=4) keeps the
    fused sparse backend at the dense reference, fp32 and quantized."""
    run_sub(_SUB_PRELUDE + """
    M = 32
    mesh = Mesh(np.array(jax.devices()[:8]), ("clients",))
    spec = MixingSpec.ring(M, self_weight=0.5)
    for q in [None,
              QuantConfig(bits=8, stochastic=False, delta_mode="lemma5")]:
        cfg = DFedAvgMConfig(eta=0.05, theta=0.9, local_steps=3, quant=q,
                             fuse_round=True)
        st_d, _ = run(dataclasses.replace(cfg, mixer_impl="dense"),
                      spec, M, 3)
        st_s, _ = run(dataclasses.replace(cfg, mixer_impl="sparse"),
                      spec, M, 3, mesh=mesh, client_axes=("clients",))
        diff = leafmax(st_d, st_s)
        tol = 1e-6 if q is None else 2.5e-3   # one quantizer step
        assert diff <= tol, (q, diff)

    # K=2 (everything deferred or fused — the scan is empty) at eta=0
    # stays bitwise against the unfused block-sharded round.
    cfg0 = DFedAvgMConfig(eta=0.0, theta=0.9, local_steps=2,
                          quant=QuantConfig(bits=8, stochastic=False,
                                            delta_mode="eq7"),
                          mixer_impl="sparse")
    st_u, _ = run(cfg0, spec, M, 2, mesh=mesh, client_axes=("clients",))
    st_f, _ = run(dataclasses.replace(cfg0, fuse_round=True), spec, M, 2,
                  mesh=mesh, client_axes=("clients",))
    assert leafmax(st_u, st_f) == 0.0
    print("OK")
    """)


def test_fused_round_kernel_structure():
    """Jaxpr structure of the ``wire="planar"`` fused round: the local
    scan runs K-2 steps; q8 lowers to EXACTLY one fused encode
    (momentum+quantize+pack) and one fused decode (dequant+mix+momentum)
    pallas_call; no standalone momentum kernel and no plain (unfused)
    codec kernel anywhere — while the unfused round still uses the plain
    codec pair."""
    run_sub(_SUB_PRELUDE + """
    M, K = 8, 5
    mesh = Mesh(np.array(jax.devices()[:M]), ("clients",))
    spec = MixingSpec.ring(M, self_weight=0.5)

    from repro.launch.hlo_stats import jaxpr_eqns, pallas_kernel_names

    def kernel_names_and_scans(step, st, batches):
        jx = jax.make_jaxpr(step)(st, batches)
        scans = [int(e.params["length"]) for e in jaxpr_eqns(jx)
                 if e.primitive.name == "scan"]
        return pallas_kernel_names(jx), scans

    def build(q, fuse):
        cfg = DFedAvgMConfig(eta=0.05, theta=0.9, local_steps=K, quant=q,
                             mixer_impl="sparse", wire="planar",
                             fuse_round=fuse)
        params, batches = problem(M, K)
        step = make_round_step(loss, cfg, spec, mesh=mesh,
                               client_axes=("clients",))
        return kernel_names_and_scans(
            step, init_round_state(params, jax.random.PRNGKey(7)), batches)

    q8 = QuantConfig(bits=8, stochastic=False, delta_mode="eq7")

    # fused q8: one fused encode + one fused decode, nothing else
    names, scans = build(q8, fuse=True)
    enc = [n for n in names if n == "momentum_quantize_pack_buffer"]
    dec = [n for n in names if n == "dequant_mix_momentum_buffer"]
    assert len(enc) == 1, names
    assert len(dec) == 1, names
    assert len(names) == 2, names           # no standalone/plain kernels
    assert K - 2 in scans, scans            # local scan shrank to K-2
    assert K not in scans, scans

    # fused fp32: no Pallas at all (XLA fuses the elementwise tail), and
    # the same K-2 scan
    names, scans = build(None, fuse=True)
    assert not names, names
    assert K - 2 in scans and K not in scans, scans

    # unfused q8 contrast: plain codec kernels, full-length scan
    names, scans = build(q8, fuse=False)
    assert "quantize_pack_buffer" in names, names
    assert "dequant_mix_buffer" in names, names
    assert not any("momentum_quantize_pack" in n for n in names), names
    assert K in scans, scans
    print("OK")
    """)
