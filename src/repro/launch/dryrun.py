import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^ MUST run before any jax import/init (device count locks on first use).

import argparse       # noqa: E402
import json           # noqa: E402
import time           # noqa: E402
import traceback      # noqa: E402
from pathlib import Path  # noqa: E402

import jax            # noqa: E402

from repro.configs import INPUT_SHAPES, get_config, list_archs  # noqa: E402
from repro.launch.build import build_step, skip_reason  # noqa: E402
from repro.launch.cost_model import (analytic_hbm_bytes,  # noqa: E402
                                     structural_costs)
from repro.launch.hlo_stats import (collect_collectives,  # noqa: E402
                                    collect_collectives_looped)
from repro.launch.mesh import (device_peaks,  # noqa: E402
                               make_production_mesh)

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"
# The chip the production meshes model (fake host devices stand in for it).
TARGET_DEVICE_KIND = "TPU v5 lite"


def validate_sharding(archs=None, model_parallels=(2, 4, 8),
                      clients=2, strategy_rules=None, verbose=True):
    """Shardability pre-flight for the 2D ``(clients, model)`` mesh: for
    every roofline config, eval_shape the abstract param pytree (no
    allocation) and build its PartitionSpecs under the strategy-A rules at
    each ``model_parallel`` degree, reporting which rule-covered dims FALL
    BACK TO REPLICATED (a dim that doesn't divide the model axis, e.g.
    smollm's 9 heads over model=2). A config whose spec construction
    RAISES is a hard failure — this is how a broken config dies at
    pre-flight instead of at ``make_client_mesh`` + first compile.

    Only mesh axis names/sizes are consulted (a lightweight stand-in
    object, not a device mesh), so this runs on any host regardless of
    device count. Returns a list of per-(arch, mp) record dicts;
    ``record["error"]`` is set on failure.
    """
    import types

    import numpy as np

    from repro.models import model as M
    from repro.sharding.rules import (RULES_A, _IS_TUPLE, shapes_and_axes,
                                      specs_for_tree, stack_shapes)

    rules = strategy_rules or RULES_A
    archs = list(archs) if archs else list_archs()
    records = []
    for arch in archs:
        cfg = get_config(arch)
        try:
            shapes, axes = shapes_and_axes(
                lambda k, cfg=cfg: M.init_model(k, cfg))
            stacked = stack_shapes(shapes, clients)
        except Exception as e:  # noqa: BLE001
            for mp in model_parallels:
                records.append({"arch": arch, "model_parallel": mp,
                                "error": f"init eval_shape: {e!r}"})
            continue
        ax_paths = jax.tree_util.tree_flatten_with_path(
            axes, is_leaf=_IS_TUPLE)[0]
        shape_leaves = jax.tree.leaves(
            stacked, is_leaf=lambda x: hasattr(x, "shape"))
        for mp in model_parallels:
            fake_mesh = types.SimpleNamespace(
                axis_names=("clients", "model"),
                devices=np.empty((clients, mp)))
            rec = {"arch": arch, "model_parallel": mp,
                   "n_leaves": len(ax_paths)}
            try:
                specs = specs_for_tree(axes, stacked, rules, fake_mesh,
                                       leading_client=("clients",))
            except Exception as e:  # noqa: BLE001
                rec["error"] = repr(e)
                records.append(rec)
                continue
            spec_leaves = jax.tree.leaves(
                specs, is_leaf=lambda s: isinstance(s, jax.sharding
                                                    .PartitionSpec))
            sharded, fallbacks = 0, []
            for (path, names), spec, shp in zip(ax_paths, spec_leaves,
                                                shape_leaves):
                for i, name in enumerate(names):
                    if name is None or name not in rules or \
                            name == "layers":
                        continue
                    entry = spec[i + 1] if len(spec) > i + 1 else None
                    ents = entry if isinstance(entry, tuple) else (entry,)
                    if "model" in ents:
                        sharded += 1
                    else:
                        fallbacks.append({
                            "leaf": jax.tree_util.keystr(path),
                            "dim": name, "size": int(shp.shape[i + 1])})
            rec.update(sharded_dims=sharded, replicated_fallbacks=fallbacks)
            records.append(rec)
            if verbose:
                fb = ", ".join(f"{f['leaf']}:{f['dim']}={f['size']}"
                               for f in fallbacks) or "none"
                print(f"[shard-ok] {arch} @ model_parallel={mp}: "
                      f"{sharded} dims sharded, replicated fallbacks: {fb}")
    return records


def model_flops(cfg, meta) -> float:
    """Analytic MODEL_FLOPS: 6*N_active*D (train) / 2*N_active*D (serve)."""
    n = cfg.n_active_params()
    d = meta["tokens_per_step"]
    return (6.0 if meta["kind"] == "train" else 2.0) * n * d


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            strategy: str | None = None, tag: str = "baseline",
            dfed=None, save: bool = True,
            cfg_overrides: dict | None = None) -> dict:
    import dataclasses
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    reason = skip_reason(cfg, shape_name)
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "tag": tag}
    if reason:
        rec["skipped"] = reason
        if save:
            OUT_DIR.mkdir(parents=True, exist_ok=True)
            out = OUT_DIR / f"{arch}__{shape_name}__{mesh_name}__{tag}.json"
            out.write_text(json.dumps(rec, indent=2, default=str))
        print(f"[skip] {arch} x {shape_name} x {mesh_name}: {reason}")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()
    kw = {"strategy": strategy} if INPUT_SHAPES[shape_name].kind == "train" \
        else {}
    if dfed is not None and INPUT_SHAPES[shape_name].kind == "train":
        kw["dfed"] = dfed
    built = build_step(cfg, mesh, shape_name, **kw)
    with jax.set_mesh(mesh):
        lowered = built.fn.lower(*built.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll_flat = collect_collectives(hlo)
    coll = collect_collectives_looped(hlo)   # trip-count-aware (per device)

    # Structural (jaxpr) costs: exact, scan-aware, GLOBAL program totals.
    t1 = time.time()
    struct = structural_costs(built.fn, *built.args)
    t_struct = time.time() - t1

    xla_flops = float(cost.get("flops", 0.0))         # per-device, loops x1
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    mf = model_flops(cfg, built.meta)

    # Roofline terms (seconds), per the brief's formulas:
    #   compute    = FLOPs / (chips * peak)     [struct = global FLOPs]
    #   memory     = bytes / (chips * HBM_bw)   [analytic HBM model —
    #                struct.bytes is an unfused upper bound, reported too]
    #   collective = wire_bytes_per_device / link_bw
    hbm_bytes = analytic_hbm_bytes(cfg, built.meta, n_chips)
    peaks = device_peaks(TARGET_DEVICE_KIND)
    compute_t = struct.flops / (n_chips * peaks["flops_bf16"])
    memory_t = hbm_bytes / (n_chips * peaks["hbm_bw"])
    coll_t = coll.wire_bytes / peaks["ici_bw_per_link"]
    terms = {"compute_s": compute_t, "memory_s": memory_t,
             "collective_s": coll_t}
    dom = max(terms, key=terms.get)

    rec.update({
        "meta": built.meta,
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "struct_s": round(t_struct, 2),
        "struct_flops_global": struct.flops,
        "struct_bytes_global_unfused_ub": struct.bytes,
        "analytic_hbm_bytes_global": hbm_bytes,
        "struct_coll_bytes_per_dev": struct.coll_bytes,
        "struct_coll_by_kind": struct.coll_by_kind,
        "xla_flops_per_device_loops_x1": xla_flops,
        "xla_bytes_per_device_loops_x1": xla_bytes,
        "collective_looped": coll.as_dict(),
        "collective_flat": coll_flat.as_dict(),
        "memory_analysis": {
            k: getattr(mem, k, None)
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
        } if mem is not None else None,
        "model_flops_total": mf,
        "useful_flops_ratio": (mf / struct.flops if struct.flops else None),
        "roofline": terms,
        "dominant": dom,
    })
    if save:
        OUT_DIR.mkdir(parents=True, exist_ok=True)
        out = OUT_DIR / f"{arch}__{shape_name}__{mesh_name}__{tag}.json"
        out.write_text(json.dumps(rec, indent=2, default=str))
    print(f"[ok] {arch} x {shape_name} x {mesh_name} ({tag}): "
          f"compile={t_compile:.1f}s Gflops/dev={struct.flops/n_chips/1e9:.1f} "
          f"GB/dev={struct.bytes/n_chips/1e9:.2f} "
          f"wire/dev={coll.wire_bytes/1e9:.3f}GB "
          f"terms(ms)=[{compute_t*1e3:.1f}/{memory_t*1e3:.1f}/{coll_t*1e3:.1f}] "
          f"dominant={dom} "
          f"useful={rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'], 3)}")
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--strategy", default=None, choices=[None, "A", "B", "B2", "B3"])
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--bits", type=int, default=32,
                    help="gossip wire quantization (train shapes)")
    ap.add_argument("--mixer", default=None,
                    choices=[None, "ring", "torus", "sparse", "dense"])
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--eta", type=float, default=1e-3)
    ap.add_argument("--validate-sharding", action="store_true",
                    help="2D-mesh pre-flight only: check every config's "
                         "abstract pytree shards under the rule set at "
                         "each --model-parallels degree, report "
                         "replicated fallbacks, exit 1 on any failure")
    ap.add_argument("--model-parallels", default="2,4,8",
                    help="comma-separated model_parallel degrees for "
                         "--validate-sharding")
    args = ap.parse_args()

    if args.validate_sharding:
        archs = None if args.arch == "all" else args.arch.split(",")
        mps = tuple(int(v) for v in args.model_parallels.split(","))
        records = validate_sharding(archs=archs, model_parallels=mps)
        errors = [r for r in records if r.get("error")]
        if errors:
            print(f"\n{len(errors)} SHARDING FAILURES:")
            for r in errors:
                print(f"  {r['arch']} @ model_parallel="
                      f"{r['model_parallel']}: {r['error']}")
            raise SystemExit(1)
        print(f"\nall {len(records)} (arch, model_parallel) combinations "
              f"shard cleanly")
        return

    dfed = None
    if args.bits < 32 or args.mixer is not None or args.local_steps != 2:
        from repro.core import DFedAvgMConfig, QuantConfig
        dfed = DFedAvgMConfig(
            eta=args.eta, theta=0.9, local_steps=args.local_steps,
            quant=QuantConfig(bits=args.bits) if args.bits < 32 else None,
            mixer_impl=args.mixer or "auto")

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    shapes = list(INPUT_SHAPES) if args.shape == "all" \
        else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    run_one(arch, shape, multi_pod=mp,
                            strategy=args.strategy, tag=args.tag,
                            dfed=dfed)
                except Exception as e:  # noqa: BLE001
                    failures.append((arch, shape, mp, repr(e)))
                    print(f"[FAIL] {arch} x {shape} x multi={mp}: {e}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nall dry-runs passed")


if __name__ == "__main__":
    main()
