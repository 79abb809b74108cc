"""Run the DFedAvgM round on the TPU once, through the training entry point,
and check what comes out.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the gossip paths across four chips

One chip: three rounds of OLMo-1B at its published widths (depth cut to
two layers) through ``repro.launch.train.main`` with the Pallas codec,
against the same rounds with the pure-XLA codec; then the q8 wire codec
kernels, compiled, against their oracles at the training run's wire
width.

Four chips, at the same widths: a one-client-per-chip sparse ring
against the dense mixer, and a 2D (clients x model) mesh against its 1D
run, each checked for client state spread over every chip.

The last line of stdout is ``{"ok": true, "device": {...}}`` when every
check passed. A failed check exits non-zero and prints no such line.
Everything runs in this one process: a chip belongs to one process.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# One chip: OLMo-1B (configs/olmo_1b.py) at its published widths, bf16,
# depth cut to 2 layers. Two clients as one block on the chip: at batch
# 4 x seq 1024 the vmapped local SGD of two clients takes ~11.6 GiB of
# the 16 GB HBM (compiled.memory_analysis() for a described v5e), so a
# third client does not fit.
TRAIN_ARGS = ["--arch", "olmo-1b", "--no-reduced", "--layers", "2",
              "--clients", "2", "--clients-per-shard", "2",
              "--mixer-impl", "sparse", "--bits", "8", "--local-steps", "2",
              "--batch", "4", "--seq", "1024", "--rounds", "3", "--seed", "0"]

# Four chips: the same model, widths and batch, with one client or (2D
# mesh) one client's model half per chip. Each arm's round step compiles for a
# described v5e:2x2 in 15-23 s on an 8-core host, at 3.2-6.9 GiB of
# temporaries per chip (compiled.memory_analysis()).
FOUR_CHIP_ARGS = TRAIN_ARGS[:TRAIN_ARGS.index("--clients")] + [
    "--bits", "8", "--local-steps", "2", "--batch", "4", "--seq", "1024",
    "--rounds", "3", "--seed", "0"]

# Per-round loss gap allowed between two runs of the same rounds that
# differ only in how the gossip is computed (Pallas vs XLA codec, sparse
# vs dense mixer, 2D vs 1D mesh). The tests bound such runs at one
# quantizer step per parameter (s = amax/127 of the round's delta, at a
# floor knife edge reached through ~ulp differences between separately
# compiled programs); a step that small on a few parameters moves the
# mean loss far below this, while a broken codec or a lost stream moves
# it at O(1e-2) or more.
LOSS_RTOL = 1e-3


def fail(msg: str) -> None:
    """Exit non-zero with ``msg`` on stderr."""
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def require_tpu(n_chips: int):
    """The platform check, before any work: no CPU fallback."""
    import jax
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"JAX found no TPU (platform {devs[0].platform!r})")
    check(len(devs) >= n_chips,
          f"needs {n_chips} chips, JAX sees {len(devs)}")
    return devs


def kernel_phase(devs) -> None:
    """The q8 round path's codec kernels, compiled (``interpret=False``),
    against their oracles in ``kernels/ref.py`` at the wire width of the
    training run."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.core import QuantConfig
    from repro.core.wire_layout import WireLayout
    from repro.kernels import ref
    from repro.kernels.dequant_mix import dequant_mix_buffer_pallas
    from repro.kernels.quantize_pack import quantize_pack_buffer_pallas
    from repro.models import model as M

    cfg = dataclasses.replace(get_config("olmo-1b"), n_layers=2)
    params = jax.eval_shape(lambda k: M.init_model(k, cfg)[0],
                            jax.random.PRNGKey(0))
    layout = WireLayout.for_tree(params, bits=8)
    per, w = layout.per, layout.total_words
    print(f"kernels: q8 wire buffer [{per}, {w}] "
          f"({layout.n_leaves} leaves, {layout.n_blocks} lane blocks); "
          f"bytes_in_use {devs[0].memory_stats()['bytes_in_use']} before")
    keys = jax.random.split(jax.random.PRNGKey(1), 6)
    x2d = jax.random.normal(keys[0], (per, w), jnp.float32) * 0.02
    scales = layout.leaf_scales(x2d, QuantConfig(bits=8))
    sblk = layout.block_scales(scales)
    noise = jax.random.uniform(keys[1], (per, w), jnp.float32)

    words = quantize_pack_buffer_pallas(x2d, sblk.reshape(1, -1), noise,
                                        bits=8, stochastic=True,
                                        interpret=False)
    oracle = ref.quantize_pack_buffer_ref(x2d, sblk, 8, noise=noise)
    n_diff = int(jnp.sum(words != oracle))
    print(f"kernels: quantize_pack_buffer words differing from oracle: "
          f"{n_diff} of {w}")
    check(n_diff == 0, "encoder words differ from the oracle")
    del noise, oracle

    k = 3
    streams = jnp.stack([words] + [
        jax.random.bits(kk, (w,), jnp.uint32) for kk in keys[2:k + 1]])
    bscales = jnp.stack([sblk] * k)
    weights = jnp.asarray([0.5, 0.25, 0.25], jnp.float32)
    out = dequant_mix_buffer_pallas(x2d, streams, bscales, weights, bits=8,
                                    interpret=False)
    o = ref.dequant_mix_buffer_ref(x2d, streams, bscales, weights, 8)
    err = float(jnp.max(jnp.abs(out - o)))
    # The bound tests/test_wire_layout.py states: same values and order,
    # FMA contraction chosen per compilation -> a few ulp.
    tol = 8 * float(np.finfo(np.float32).eps) * (
        float(jnp.max(jnp.abs(o))) + 1.0)
    print(f"kernels: dequant_mix_buffer max |kernel - oracle| = {err:.3e} "
          f"(bound {tol:.3e})")
    check(err <= tol, "decode differs from the oracle beyond a few ulp")
    peak = devs[0].memory_stats()["peak_bytes_in_use"]
    print(f"kernels: peak_bytes_in_use {peak} (whole process)")


def train(argv: list) -> "RunResult":
    from repro.launch.train import main
    print(f"train: {' '.join(argv)}", flush=True)
    return main(argv)


def round_losses(res) -> list:
    """Per-round losses of a run, checked finite, with each round's time
    printed (round 1 includes compilation)."""
    losses = []
    for r in res.rounds:
        note = " (includes compile)" if r["t"] == 0 else ""
        print(f"  round {r['t'] + 1}: loss {r['loss']:.6f}  "
              f"wall {r['round_s']:.3f} s{note}")
        losses.append(r["loss"])
    check(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")
    return losses


def compare(name: str, got: list, want: list) -> None:
    gap = max(abs(a - b) / max(abs(b), 1e-12) for a, b in zip(got, want))
    print(f"{name}: max relative loss gap {gap:.3e} (bound {LOSS_RTOL})")
    check(len(got) == len(want) and gap <= LOSS_RTOL,
          f"{name}: losses {got} vs {want}")


def train_phase(devs) -> None:
    """Runs before the kernel checks: a process cannot reset its peak, so
    the peak read after the Pallas-codec run is that run's own."""
    n_rounds = int(TRAIN_ARGS[TRAIN_ARGS.index("--rounds") + 1])
    res = train(TRAIN_ARGS + ["--wire", "planar"])
    check(len(res.rounds) == n_rounds, "a round went unlogged")
    planar = round_losses(res)
    lowered = res.step.lower(res.state, res.batches)
    n_kernels = lowered.as_text().count("tpu_custom_call")
    print(f"train: round program has {n_kernels} tpu_custom_call sites")
    check(n_kernels > 0, "the round program runs no Pallas kernel")
    ma = lowered.compile().memory_analysis()
    print(f"train: round step memory_analysis: arguments "
          f"{ma.argument_size_in_bytes} B, temporaries "
          f"{ma.temp_size_in_bytes} B, outputs {ma.output_size_in_bytes} B "
          f"({ma.alias_size_in_bytes} B aliased to arguments)")
    stats = devs[0].memory_stats()
    print(f"train: device_kind {devs[0].device_kind}, peak_bytes_in_use "
          f"{stats['peak_bytes_in_use']} of bytes_limit "
          f"{stats['bytes_limit']} (the planar run's own peak)")
    del res, lowered

    ref_res = train(TRAIN_ARGS + ["--wire", "seq"])
    seq = round_losses(ref_res)
    del ref_res
    compare("planar vs seq", planar, seq)
    peak = devs[0].memory_stats()["peak_bytes_in_use"]
    print(f"train: peak_bytes_in_use {peak} after the seq run")


def spread_check(name: str, res, devs) -> None:
    """Client state on every chip of the mesh, and memory in use spread
    over the chips rather than piled on the first."""
    import jax
    for leaf in jax.tree.leaves(res.state.params):
        check(leaf.sharding.device_set == set(devs),
              f"{name}: a param leaf lives on "
              f"{sorted(d.id for d in leaf.sharding.device_set)} only")
    in_use = [d.memory_stats()["bytes_in_use"] for d in devs]
    peak = [d.memory_stats()["peak_bytes_in_use"] for d in devs]
    print(f"{name}: bytes_in_use per chip {in_use}, peak_bytes_in_use "
          f"{peak}")
    check(min(in_use) >= 0.5 * max(in_use),
          f"{name}: device memory piled on some chips: {in_use}")


def four_chip_phase(devs) -> None:
    devs = devs[:4]
    # (a) one client per chip, sparse ring ppermutes over ICI vs the
    # dense mixer at the same seed, its clients on the same four chips.
    sparse = train(FOUR_CHIP_ARGS + ["--clients", "4",
                                     "--clients-per-shard", "1",
                                     "--mixer-impl", "sparse"])
    spread_check("sparse ring", sparse, devs)
    ring = round_losses(sparse)
    del sparse
    dense = train(FOUR_CHIP_ARGS + ["--clients", "4",
                                    "--mixer-impl", "dense"])
    spread_check("dense", dense, devs)
    compare("sparse ring vs dense", ring, round_losses(dense))
    del dense
    # (b) 2D (clients x model) mesh vs the 1D run of the same clients.
    mesh2d = train(FOUR_CHIP_ARGS + ["--clients", "2", "--model-parallel",
                                     "2", "--mixer-impl", "sparse"])
    spread_check("2D mesh", mesh2d, devs)
    two_d = round_losses(mesh2d)
    del mesh2d
    one_d = round_losses(train(FOUR_CHIP_ARGS + ["--clients", "2",
                                                 "--mixer-impl", "sparse"]))
    compare("2D vs 1D", two_d, one_d)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip gossip arms")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no repro package under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))
    n_chips = 4 if args.four_chips else 1
    devs = require_tpu(n_chips)

    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}")
    print(f"device: {devs[0].platform} {devs[0].device_kind} x {len(devs)}")
    if args.four_chips:
        four_chip_phase(devs)
    else:
        train_phase(devs)
        kernel_phase(devs)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": n_chips}}))


if __name__ == "__main__":
    main()
