"""Readings that set a cell's limits: the program's sound runs, the
control and the planted faults, each against the float32 reference, on
the chip at the cell's own size. The benchmark's runs do not run this.

    python chipbench/control.py --workload <cell> --seeds 1 2 3 \
        [--what program control half_batch no_exchange]

``program`` is a run of the harness with a one-second window: the
timed path's checked rounds against the reference, as every run
compares them, for many seeds in one process (the set-up is paid once
for the compile).

The control is the reference put in the program's place and computed
with float8 (e4m3) matmul operands, the precision below the bfloat16
the configuration states. The faults are planted in the reference put
in the program's place: ``half_batch`` takes each gradient over half of
the rows, ``no_exchange`` mixes with the identity. (A state left
unchanged reads 1 on ``change1`` by the measure itself.) What stands in
the program's place draws its wire's noise apart from the reference, as
the program does. The reference runs on one chip, whatever chips the
cell asks for. One JSON line per seed and reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]
# The numbers compared come from the checked rounds of set-up, so a
# ``program`` reading needs no longer window than this.
PROGRAM_WINDOW_S = 1.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--what", nargs="+",
                    default=["control", "half_batch", "no_exchange"])
    args = ap.parse_args(argv)
    import jax

    import check
    import harness
    import spec
    import traffic
    cell = spec.Cell(spec.load(), args.workload)
    harness.require_chips(cell.chips if "program" in args.what else 1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    t, ref = cell.traffic, cell.reference()
    for seed in args.seeds:
        if "program" in args.what:
            t0 = time.perf_counter()
            out = harness.run(cell, seed, PROGRAM_WINDOW_S, False,
                              process_age=lambda: time.perf_counter() - t0)
            print(json.dumps({"seed": seed, "what": "program",
                              "seconds": time.perf_counter() - t0,
                              "correct": out["correct"],
                              "numbers": {k: v["value"] for k, v
                                          in out["checks"].items()}}),
                  flush=True)
        others = [w for w in args.what if w != "program"]
        if not others:
            continue
        key = traffic.seed_key(seed)
        k_w, k_data, _, k_q, k_other = (jax.random.fold_in(key, i)
                                        for i in range(5))

        def feed(r):
            return traffic.round_batches(
                k_data, r, m=t["clients"], K=t["local_steps"],
                batch=t["batch"], seq=t["seq"],
                vocab=cell.model["vocab_size"])

        t0 = time.perf_counter()
        base = check.reference_readings(ref, cell.model, t, k_w, feed,
                                        q_key=k_q)
        print(json.dumps({"seed": seed, "what": "reference",
                          "seconds": time.perf_counter() - t0,
                          "losses": base["losses"].tolist(),
                          "n1": base["n1"].tolist(),
                          "g0": base["g0"].tolist()}), flush=True)
        for what in others:
            kw = ({"fp8": True} if what == "control" else {"fault": what})
            t0 = time.perf_counter()
            other = check.reference_readings(ref, cell.model, t, k_w, feed,
                                             q_key=k_other, **kw)
            print(json.dumps({"seed": seed, "what": what,
                              "seconds": time.perf_counter() - t0,
                              "numbers": check.numbers(other, base)}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
