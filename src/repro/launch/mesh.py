"""Production mesh construction (v5e pods).

Defined as FUNCTIONS so importing this module never touches jax device
state — jax locks the device count at first backend init, and the dry-run
must set XLA_FLAGS before that happens.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh


def _auto_mesh(shape, axes) -> Mesh:
    """Mesh over the first ``prod(shape)`` devices with every axis Auto
    (GSPMD-partitioned), which ``jax.sharding.Mesh`` gives by default and
    ``jax.make_mesh`` does not."""
    from jax.experimental import mesh_utils
    return Mesh(mesh_utils.create_device_mesh(shape), axes)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips (16, 16) ("data", "model").
    Multi-pod: 2 pods = 512 chips (2, 16, 16) ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(shape=(4, 2), axes=("data", "model")):
    """Small host-device mesh for CPU multi-device tests."""
    return _auto_mesh(shape, axes)


# (m, clients_per_shard) combinations already warned about — the dense
# fallback is worth exactly one loud line per shape, not one per round.
_FALLBACK_WARNED: set = set()


def make_client_mesh(m: int, axis: str = "clients",
                     clients_per_shard: int = 1,
                     model_parallel: int = 1,
                     model_axis: str = "model"):
    """Client mesh for the sparse GossipPlan backend: each of the
    ``m // clients_per_shard`` client shards holds a CONTIGUOUS BLOCK of
    ``clients_per_shard`` clients (``clients_per_shard=1`` is the classic
    one-client-per-device layout). ``model_parallel > 1`` composes the
    client axis with a tensor-parallel ``model`` axis into a 2D
    ``(clients, model)`` mesh of ``n_shards * model_parallel`` devices:
    each device then holds only its model slice of its client block, and
    the sparse executor ships only that slice over boundary ppermutes
    (per-device wire drops ~linearly with ``model_parallel``). Returns
    ``None`` when the host has too few devices — with a ONE-TIME warning
    naming the dense fallback and the flags that control it (this used to
    happen silently)."""
    import warnings

    import numpy as np

    if clients_per_shard < 1 or m % clients_per_shard:
        raise ValueError(
            f"clients_per_shard={clients_per_shard} must divide m={m}")
    if model_parallel < 1:
        raise ValueError(f"model_parallel={model_parallel} must be >= 1")
    n_shards = m // clients_per_shard
    n_devices = n_shards * model_parallel
    devs = jax.devices()
    if len(devs) < n_devices:
        key = (m, clients_per_shard, model_parallel)
        if key not in _FALLBACK_WARNED:
            _FALLBACK_WARNED.add(key)
            warnings.warn(
                f"make_client_mesh: m={m} clients at clients_per_shard="
                f"{clients_per_shard}, model_parallel={model_parallel} "
                f"needs {n_devices} devices but this host has {len(devs)} "
                f"({n_devices - len(devs)} short); returning None, so "
                f"callers FALL BACK TO THE DENSE MIXER (all-gather "
                f"traffic, not O(degree) ppermutes) and any --placement "
                f"partition request cannot apply (placement permutes "
                f"block lanes, which only exist on the sparse mesh "
                f"backend). Raise --clients-per-shard so that "
                f"m/clients_per_shard * model_parallel <= {len(devs)}, "
                f"or pass --mixer-impl dense to make the fallback "
                f"explicit.",
                UserWarning, stacklevel=2)
        return None
    if model_parallel == 1:
        return Mesh(np.array(devs[:n_shards]), (axis,))
    grid = np.array(devs[:n_devices]).reshape(n_shards, model_parallel)
    return Mesh(grid, (axis, model_axis))


def resident_lane_capacity(bytes_per_client: int,
                           budget_bytes: int | None = None,
                           overhead: float = 4.0,
                           model_parallel: int = 1) -> int:
    """How many client lanes fit device memory — the pooled-execution
    sizing heuristic (``--resident-lanes`` defaults from this).

    ``bytes_per_client`` is one client's parameter bytes;  ``overhead``
    budgets the working set per lane (params + momentum + grads + update
    temporaries ~= 4x params). ``budget_bytes`` defaults to the first
    device's reported memory (v5e: 16 GiB HBM) or 2 GiB when the backend
    doesn't report one (CPU). On a 2D ``(clients, model)`` mesh each
    device resident-holds only ``1/model_parallel`` of every lane's
    params, so capacity grows ~linearly with ``model_parallel``. Always
    returns at least 1.
    """
    if model_parallel < 1:
        raise ValueError(f"model_parallel={model_parallel} must be >= 1")
    if budget_bytes is None:
        dev = jax.devices()[0]
        budget_bytes = (dev.memory_stats() or {}).get("bytes_limit")
        if not budget_bytes:
            if dev.platform != "cpu":
                raise RuntimeError(
                    f"{dev.device_kind} reports no bytes_limit; pass "
                    f"--resident-lanes to size the pool explicitly")
            budget_bytes = 2 << 30
    per_device = -(-bytes_per_client // model_parallel)
    return max(1, int(budget_bytes / (overhead * per_device)))


# Published per-chip peaks, keyed by ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s ICI per chip over 4 links (50 GB/s per link). JAX names
# the v5e "TPU v5 lite".
DEVICE_PEAKS = {"TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9,
                                "ici_bw_per_link": 50e9}}


def device_peaks(device_kind: str) -> dict:
    """Peak FLOP/s and bytes/s of one chip of ``device_kind``; a kind not
    in :data:`DEVICE_PEAKS` is an error, never a default."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: "
                         f"{sorted(DEVICE_PEAKS)}") from None
