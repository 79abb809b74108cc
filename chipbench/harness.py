"""One run of one cell: set-up through the program's own entry point, a
timed window of rounds, and the check that decides ``correct``.

Set-up (``setup_s``, from process start to the window):

1. the platform check: a TPU with the chips the cell asks for, or exit
   non-zero with no result;
2. ``repro.launch.train.main`` with the cell's arguments and
   ``--rounds 1``: it builds the jitted, donated round step, compiles
   it (or loads it from the persistent compilation cache) and runs one
   round;
3. the benchmark's own weights, made from ``--seed`` on the device in
   one jitted call, take the place of the program's, in the same
   layout, dtype and sharding;
4. the cell's first checked rounds through the step the window drives,
   on the window's feed; their losses and parameter changes are what
   the reference is compared with after the window.

The window drives the same step on the same state for ``--seconds``,
keeping one round in flight (dispatch round t+1, then read round t's
loss), and ends when the last dispatched round is done. Nothing
compiles in it: the step's jit cache must not grow. With ``--trace 1``
the window runs under the profiler and the per-layer metrics are read
from its trace.

After the window the peak device memory is read, the program's state is
freed, and the plain float32 reference replays the first rounds.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager, nullcontext

import numpy as np

import check
import spec as spec_mod
import traffic

FAULTS = ("unchanged", "half_batch", "no_exchange")


def parse(argv):
    ap = argparse.ArgumentParser(prog="chipbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(n: int):
    """The cell's chips, on an accelerator; exit 2 with no result
    otherwise. There is no CPU fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        print("chipbench: JAX found no accelerator", file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < n:
        print(f"chipbench: the cell needs {n} chips, JAX sees "
              f"{len(devs)}", file=sys.stderr)
        raise SystemExit(2)
    return devs[:n]


def check_model(cell) -> None:
    """The program's registered architecture, cut as the configuration
    file says, must have the file's sizes."""
    import dataclasses
    from repro.configs import get_config
    from repro.configs import reduced
    a = cell.config["train_args"]
    arch = get_config(a[a.index("--arch") + 1])
    if "--no-reduced" not in a:
        arch = reduced(arch)
    arch = dataclasses.replace(arch, n_layers=cell.model["n_layers"])
    for key, want in cell.model.items():
        have = getattr(arch, key, None)
        if have != want:
            raise SystemExit(f"chipbench: {cell.config_entry['name']} "
                             f"states {key}={want!r}, the program's "
                             f"architecture has {have!r}")


def memory_plan(step, state, batches) -> tuple[dict, str]:
    """The compiler's plan for the round step (arguments, temporaries,
    outputs: the device's own peak counter misses temporaries), and the
    compiled program's HLO text."""
    compiled = step.lower(state, batches).compile()
    ma = compiled.memory_analysis()
    plan = {k: int(getattr(ma, f"{k}_size_in_bytes"))
            for k in ("argument", "temp", "output", "alias",
                      "generated_code")}
    print(f"chipbench: round step memory plan (bytes per chip): "
          f"{json.dumps(plan)}", flush=True)
    return plan, compiled.as_text()


def faulted(step, fault: str | None):
    """The timed path, broken underneath in one of :data:`FAULTS` (for
    the tests that show the check catches it); ``None`` leaves it be."""
    import jax
    import jax.numpy as jnp
    if fault is None or fault == "no_exchange":
        return step
    if fault == "unchanged":
        def unchanged(state, batches):
            _, met = step(jax.tree.map(jnp.copy, state), batches)
            return state, met
        return unchanged
    if fault == "half_batch":
        def half(state, batches):
            def dup(a):
                h = a.shape[2] // 2
                return jnp.concatenate([a[:, :, :h], a[:, :, :h]], axis=2)
            return step(state, jax.tree.map(dup, batches))
        return half
    raise ValueError(f"unknown fault {fault!r}")


@contextmanager
def _exchange_left_out(on: bool):
    """Plant the ``no_exchange`` fault: the program's ring is built with
    self weight 1, so every client keeps its own model (the program
    refuses that matrix, so its check is lifted for the build)."""
    if not on:
        yield
        return
    from unittest import mock
    from repro.core import topology
    with mock.patch.object(topology, "check_mixing_matrix",
                           lambda *a, **k: None):
        yield


@contextmanager
def profiled(on: bool):
    """The profiler around the window, writing to a scratch directory
    that is removed afterwards; yields the directory (or None)."""
    if not on:
        yield None
        return
    import jax
    d = tempfile.mkdtemp(prefix="chipbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    with jax.profiler.trace(d, profiler_options=opts):
        yield d


def annotate(name: str, on: bool):
    if not on:
        return nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def device_record(devs, peak: int) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def run(cell, seed: int, seconds: float, trace: bool, *, process_age,
        require_chip: bool = True, fault: str | None = None) -> dict:
    """One run of ``cell``; returns the result object (``checks`` last)."""
    import jax
    if require_chip:
        devs = require_chips(cell.chips)
    else:
        devs = jax.devices()[:cell.chips]
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import jax.numpy as jnp
    from repro.launch import train as program

    check_model(cell)
    t = cell.traffic
    check.mixing_matrix(t)
    ref = cell.reference()
    args = cell.train_args()
    if fault == "no_exchange":
        args[args.index("--self-weight") + 1] = "1.0"
    with _exchange_left_out(fault == "no_exchange"):
        res = program.main(args)
    step = res.step
    plan, hlo = memory_plan(step, res.state, res.batches)
    n_compiled = step._cache_size()

    key = traffic.seed_key(seed)
    k_w, k_data, k_rng = (jax.random.fold_in(key, i) for i in range(3))
    m = t["clients"]
    shard = jax.tree.map(lambda a: a.sharding, res.state.params)
    make_params = jax.jit(
        lambda k: jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (m,) + a.shape),
            ref.init(k, cell.model)), out_shardings=shard)
    params = make_params(k_w)
    for mine, theirs in zip(jax.tree.leaves(params),
                            jax.tree.leaves(res.state.params)):
        if (mine.shape, mine.dtype) != (theirs.shape, theirs.dtype):
            raise SystemExit("chipbench: the reference's weights do not "
                             "match the program's parameter layout")
    if jax.tree.structure(params) != jax.tree.structure(res.state.params):
        raise SystemExit("chipbench: parameter tree differs from the "
                         "program's")
    state = res.state._replace(
        params=params,
        rng=jax.device_put(k_rng, res.state.rng.sharding),
        round=jax.device_put(jnp.zeros((), jnp.int32),
                             res.state.round.sharding))
    del res, params

    def feed(r):
        return traffic.round_batches(
            k_data, r, m=m, K=t["local_steps"], batch=t["batch"],
            seq=t["seq"], vocab=cell.model["vocab_size"])

    step_fn = faulted(step, fault)
    losses = []
    n_check = check.CHECK_ROUNDS
    for r in range(n_check):
        tr = time.perf_counter()
        state, met = step_fn(state, feed(r))
        losses.append(float(met["loss"]))
        print(f"chipbench: check round {r + 1} loss {losses[-1]!r} "
              f"{time.perf_counter() - tr:.4f} s", flush=True)
        if r == 0:
            n1, _ = check.change_readings(state.params, k_w, ref,
                                          cell.model)
    n3, spread = check.change_readings(state.params, k_w, ref, cell.model)
    prog = {"losses": np.asarray(losses), "n1": n1, "n3": n3,
            "spread": spread}
    if step._cache_size() != n_compiled:
        raise SystemExit("chipbench: the round step compiled again in "
                         "set-up: the benchmark's state differs in form "
                         "from the program's")

    # ---- the window -----------------------------------------------------
    setup_s = process_age()
    print(f"chipbench: setup_s {setup_s!r}", flush=True)
    first = n_check
    n = 0
    prev = None
    round_loss = []
    with profiled(trace) as tdir:
        t0 = time.perf_counter()
        while True:
            with annotate("bench/data", trace):
                b = feed(first + n)
            with annotate("bench/dispatch", trace):
                state, met = step_fn(state, b)
            n += 1
            if prev is not None:
                with annotate("bench/wait", trace):
                    round_loss.append(float(prev["loss"]))
            prev = met
            if time.perf_counter() - t0 >= seconds:
                break
        with annotate("bench/wait", trace):
            round_loss.append(float(prev["loss"]))
            jax.block_until_ready(state)
        window_s = time.perf_counter() - t0
    if step._cache_size() != n_compiled:
        raise SystemExit("chipbench: the round step compiled inside the "
                         "window")
    failed = int(sum(not np.isfinite(x) for x in round_loss))
    print(f"chipbench: window {n} rounds in {window_s!r} s "
          f"({window_s / n:.4f} s a round); losses first "
          f"{round_loss[0]!r} last {round_loss[-1]!r}", flush=True)

    peak = max(int(d.memory_stats().get("peak_bytes_in_use", 0))
               if d.memory_stats() else 0 for d in devs)
    device = device_record(devs, peak)
    tokens = n * cell.tokens_per_round()
    out = {"correct": None, "attempted": n, "failed": failed}
    if trace:
        import trace_reduce
        try:
            red = trace_reduce.reduce_dir(
                tdir, n_chips=cell.chips, scopes=trace_reduce.hlo_scopes(hlo))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        ctx = {"rounds": n, "tokens": tokens, "chips": cell.chips,
               "model": cell.model, "traffic": t,
               "kind": devs[0].device_kind}
        metrics = {}
        for mdef in cell.per_layer:
            value = cell.reader(mdef["name"])(red, ctx)
            if value is not None:
                metrics[mdef["name"]] = {"value": value,
                                         "unit": mdef["unit"]}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = red.breakdown()
    else:
        e2e = {"tokens_per_s_per_chip": tokens / window_s / cell.chips,
               "setup_s": setup_s}
        out["metrics"] = {m_["name"]: {"value": e2e[m_["name"]],
                                       "unit": m_["unit"]}
                          for m_ in cell.end_to_end}
        out["device"] = device
    out["plan_bytes"] = plan

    # ---- the check, after the window ------------------------------------
    del state, met, prev, b
    gc.collect()
    tc = time.perf_counter()
    ref_read = check.reference_readings(ref, cell.model, t, k_w, feed,
                                        q_key=jax.random.fold_in(key, 3))
    nums = check.numbers(prog, ref_read)
    ok, checks = check.verdict(nums, cell.limits)
    print(f"chipbench: reference {time.perf_counter() - tc:.2f} s; "
          f"losses program {prog['losses'].tolist()} reference "
          f"{ref_read['losses'].tolist()}", flush=True)
    for name, r in (("program", prog), ("reference", ref_read)):
        print(f"chipbench: {name} change norms after round 1 "
              f"{np.round(r['n1'][0], 6).tolist()} after round "
              f"{n_check} {np.round(r['n3'][0], 6).tolist()} "
              f"spread {np.round(r['spread'], 6).tolist()}", flush=True)
    out["correct"] = bool(ok and failed == 0)
    out["checks"] = checks
    return out


def print_result(out: dict) -> None:
    """The checks as the last lines of standard error, then the result
    as the last line of standard output."""
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(argv, *, process_age) -> int:
    args = parse(argv)
    bench = spec_mod.load()
    cell = spec_mod.Cell(bench, args.workload)
    out = run(cell, args.seed, args.seconds, bool(args.trace),
              process_age=process_age)
    print_result(out)
    return 0
