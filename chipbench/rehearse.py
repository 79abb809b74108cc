"""Compile each cell's round step for a TPU v5e that is described, not
attached, before any chip time is spent: what the chip's compiler would
refuse (a kernel's tiling, a program that does not fit) it refuses here.

    JAX_PLATFORMS=cpu python chipbench/rehearse.py [cell ...]

Builds the step as ``repro.launch.train`` does for the cell (same
architecture cut, mixer, wire bits, clients per shard), with the Pallas
codec kernels compiled for the chip, on one described chip or on the
described ``v5e:2x2`` for a four-chip cell, and prints
``memory_analysis()`` and the kernel and collective counts per cell.
The TPU compiler must be installed; nothing runs.
"""
from __future__ import annotations

import dataclasses
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def compile_cell(cell, topo):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.core import (DFedAvgMConfig, MixingSpec, QuantConfig,
                            RoundState, make_round_step)
    from repro.models import model as M

    t, a = cell.traffic, cell.config["train_args"]
    arch = dataclasses.replace(get_config(a[a.index("--arch") + 1]),
                               n_layers=cell.model["n_layers"], remat=False)
    m, cps = t["clients"], t["clients_per_shard"]
    mesh = Mesh(np.array(topo.devices[:m // cps]), ("clients",))
    quant = QuantConfig(bits=t["bits"]) if t["bits"] < 32 else None
    dfed = DFedAvgMConfig(eta=t["eta"], theta=t["theta"],
                          local_steps=t["local_steps"], quant=quant,
                          mixer_impl=t["mixer"], wire="planar")
    spec = MixingSpec.ring(m, self_weight=t["self_weight"])
    loss = lambda p, b, r: M.loss_fn(p, arch, b, r)
    step = jax.jit(make_round_step(loss, dfed, spec, mesh=mesh,
                                   client_axes=("clients",)),
                   donate_argnums=(0,))
    clients = NamedSharding(mesh, P("clients"))
    rep = NamedSharding(mesh, P())
    shapes = jax.eval_shape(lambda k: M.init_model(k, arch)[0],
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        (m,) + s.shape, s.dtype, sharding=clients), shapes)
    state = RoundState(
        params=params,
        rng=jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep),
        round=jax.ShapeDtypeStruct((), jnp.int32, sharding=rep))
    bshape = (m, t["local_steps"], t["batch"], t["seq"])
    batches = {k: jax.ShapeDtypeStruct(bshape, jnp.int32, sharding=rep)
               for k in ("tokens", "targets")}
    return step.lower(state, batches).compile()


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    import spec
    from repro.kernels import ops
    # Code that asks the backend sees the CPU here; the kernels are
    # compiled for the described chip.
    ops.default_interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    bench = spec.load()
    names = (argv or sys.argv[1:]) or [w["name"] for w in bench["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in names:
        cell = spec.Cell(bench, name)
        t0 = time.perf_counter()
        compiled = compile_cell(cell, topo)
        ma = compiled.memory_analysis()
        txt = compiled.as_text()
        gib = lambda b: f"{b / 2**30:.2f} GiB"
        print(f"{name}: compiled in {time.perf_counter() - t0:.1f} s; per "
              f"chip arguments {gib(ma.argument_size_in_bytes)}, "
              f"temporaries {gib(ma.temp_size_in_bytes)}, outputs "
              f"{gib(ma.output_size_in_bytes)}; tpu_custom_call "
              f"{len(re.findall(r'custom_call_target=\"tpu_custom_call\"', txt))}"
              f", collective-permute "
              f"{len(re.findall(r'collective-permute-start', txt))}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
