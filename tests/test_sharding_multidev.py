"""Multi-device correctness: run in a SUBPROCESS with 8 host devices (the
main test process must keep seeing 1 device — see conftest note)."""
import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    # A CPU run with fake host devices: never the parent's accelerator.
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_ring_mixer_matches_dense_on_mesh():
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.core import MixingSpec, QuantConfig
        from repro.core.mixing import (make_ring_mixer, mix_dense,
                                       _mix_dense_quantized)
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh((8,), ("clients",))
        m, d = 8, 65
        spec = MixingSpec.ring(m)
        x = jax.random.normal(jax.random.PRNGKey(0), (m, d))
        z = jax.random.normal(jax.random.PRNGKey(1), (m, d))
        with jax.set_mesh(mesh):
            ring = make_ring_mixer(spec, mesh, ("clients",))
            o1 = jax.jit(lambda zz: ring(None, zz))({"w": z})["w"]
        o2 = mix_dense(spec.W, {"w": z})["w"]
        err = float(jnp.max(jnp.abs(o1 - o2)))
        assert err < 1e-5, err
        for mode in ("eq7", "lemma5"):
            qc = QuantConfig(bits=8, stochastic=False, delta_mode=mode)
            with jax.set_mesh(mesh):
                rq = make_ring_mixer(spec, mesh, ("clients",), quant=qc)
                q1 = jax.jit(lambda a, b, k: rq(a, b, k))(
                    {"w": x}, {"w": z}, jax.random.PRNGKey(2))["w"]
            q2 = _mix_dense_quantized(spec.W, {"w": x}, {"w": z}, qc,
                                      jax.random.PRNGKey(2))["w"]
            err = float(jnp.max(jnp.abs(q1 - q2)))
            assert err < 1e-5, (mode, err)
        print("RING_OK")
    """)
    assert "RING_OK" in out


def test_quantized_wire_is_u32_in_hlo():
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.core import MixingSpec, QuantConfig
        from repro.core.mixing import make_ring_mixer
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh((8,), ("clients",))
        spec = MixingSpec.ring(8)
        qc = QuantConfig(bits=8, stochastic=False, delta_mode="eq7")
        rq = make_ring_mixer(spec, mesh, ("clients",), quant=qc)
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 1024))
        with jax.set_mesh(mesh):
            txt = jax.jit(lambda a, b, k: rq(a, b, k)).lower(
                {"w": x}, {"w": x}, jax.random.PRNGKey(1)
            ).compile().as_text()
        perms = [l for l in txt.splitlines() if "collective-permute(" in l]
        u32 = [l for l in perms if " u32[" in l or "u32[" in l.split("=")[1][:16]]
        assert perms, "no collective-permutes found"
        assert u32, "no u32 wire permutes found: " + perms[0]
        print("WIRE_OK", len(perms), len(u32))
    """)
    assert "WIRE_OK" in out


def test_sharded_train_round_matches_single_device():
    """The full DFedAvgM round under pjit+shard_map on an 8-device mesh is
    numerically identical to the single-device dense reference."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import (DFedAvgMConfig, MixingSpec, QuantConfig,
                                init_round_state, make_round_step)
        m, d = 8, 33
        cs = jax.random.normal(jax.random.PRNGKey(1), (m, d))
        def loss_fn(p, b, r):
            return 0.5 * jnp.sum((p["w"] - b["c"]) ** 2)
        batches = {"c": jnp.broadcast_to(cs[:, None], (m, 4, d))}
        spec = MixingSpec.ring(m)
        cfg = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4,
                             quant=QuantConfig(bits=8, stochastic=False))
        # reference: dense mixer, single device
        step_ref = jax.jit(make_round_step(loss_fn, cfg, spec))
        s_ref = init_round_state({"w": jnp.zeros((m, d))},
                                 jax.random.PRNGKey(7))
        # sharded: ring mixer via shard_map
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh((8,), ("clients",))
        pspecs = {"w": P("clients", None)}
        cfg_r = DFedAvgMConfig(eta=0.05, theta=0.5, local_steps=4,
                               quant=QuantConfig(bits=8, stochastic=False),
                               mixer_impl="ring")
        step_sh = make_round_step(loss_fn, cfg_r, spec, mesh=mesh,
                                  client_axes=("clients",),
                                  param_specs=pspecs)
        with jax.set_mesh(mesh):
            step_sh = jax.jit(step_sh)
            s_sh = init_round_state(
                {"w": jax.device_put(jnp.zeros((m, d)),
                                     NamedSharding(mesh, P("clients", None)))},
                jax.random.PRNGKey(7))
            for _ in range(5):
                s_ref, _ = step_ref(s_ref, batches)
                s_sh, _ = step_sh(s_sh, batches)
        err = float(jnp.max(jnp.abs(s_ref.params["w"] - s_sh.params["w"])))
        assert err < 1e-4, err
        print("ROUND_OK", err)
    """)
    assert "ROUND_OK" in out


def test_dryrun_tiny_mesh_all_kinds():
    """dryrun builders lower+compile on a small mesh for one arch of each
    family (fast proxy for the 512-dev production dry-run)."""
    out = run_sub("""
        import jax
        from repro.configs import get_config, reduced
        from repro.configs.base import InputShape
        from repro.launch.build import (build_train_step, build_decode_step,
                                        build_prefill_step)
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh((4, 2), ("data", "model"))
        for arch in ("smollm-135m", "mamba2-780m", "qwen3-moe-30b-a3b",
                     "zamba2-1.2b", "whisper-tiny"):
            cfg = reduced(get_config(arch))
            with jax.set_mesh(mesh):
                b = build_train_step(cfg, mesh,
                                     InputShape("t", 64, 8, "train"))
                b.fn.lower(*b.args).compile()
                b = build_decode_step(cfg, mesh,
                                      InputShape("d", 128, 8, "decode"))
                b.fn.lower(*b.args).compile()
                b = build_prefill_step(cfg, mesh,
                                       InputShape("p", 128, 8, "prefill"))
                b.fn.lower(*b.args).compile()
            print("OK", arch)
        print("BUILD_OK")
    """, timeout=1800)
    assert "BUILD_OK" in out


def test_torus_mixer_matches_dense_both_layouts():
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.core import MixingSpec
        from repro.core.mixing import make_torus_mixer, mix_dense
        z = jax.random.normal(jax.random.PRNGKey(1), (8, 33))
        spec = MixingSpec.torus(2, 4)
        ref = mix_dense(spec.W, {"w": z})["w"]
        from repro.launch.mesh import make_test_mesh
        m1 = make_test_mesh((8,), ("clients",))
        mx = make_torus_mixer(spec, m1, ("clients",))
        with jax.set_mesh(m1):
            o1 = jax.jit(lambda zz: mx(None, zz))({"w": z})["w"]
        assert float(jnp.max(jnp.abs(o1 - ref))) < 1e-5
        m2 = make_test_mesh((2, 4), ("pod", "data"))
        mx2 = make_torus_mixer(spec, m2, ("pod", "data"))
        with jax.set_mesh(m2):
            o2 = jax.jit(lambda zz: mx2(None, zz))({"w": z})["w"]
        assert float(jnp.max(jnp.abs(o2 - ref))) < 1e-5
        print("TORUS_OK")
    """)
    assert "TORUS_OK" in out
