"""The Pallas kernels of the round path, compiled for a TPU v5e that is
described, not attached: interpret mode (every other kernel test) accepts
block shapes and integer ops the chip's compiler refuses. Widths are
OLMo-1B's published ones, cut to two layers as in ``chip_smoke.py``.
The dense reference mixer is compiled too: the chip's default f32
matmul precision (one bf16 pass) is invisible on the CPU.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every pytest-xdist worker
imports this file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import (MixerConfig, MixingSpec, QuantConfig,
                        TopologySchedule, make_mixer)
from repro.core.mixing import _mix_dense_quantized, mix_dense
from repro.core.wire_layout import WireLayout
from repro.kernels.dequant_mix import (dequant_mix_buffer_pallas,
                                       dequant_mix_momentum_buffer_pallas)
from repro.kernels.ops import momentum_update_flat
from repro.kernels.quantize_pack import (momentum_quantize_pack_buffer_pallas,
                                         quantize_pack_buffer_pallas)
from repro.models import model as M


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        # A compile for a described chip is written to the persistent
        # cache but cannot be read back without one.
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _olmo_layout(bits: int) -> WireLayout:
    cfg = dataclasses.replace(get_config("olmo-1b"), n_layers=2)
    params = jax.eval_shape(lambda k: M.init_model(k, cfg)[0],
                            jax.random.PRNGKey(0))
    return WireLayout.for_tree(params, bits=bits)


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("bits", (8, 4))
def test_quantize_pack_buffer_compiles_at_olmo_width(one_chip, bits):
    lay = _olmo_layout(bits)
    per, w = lay.per, lay.total_words
    txt = _compiled_text(
        lambda x, s, n: quantize_pack_buffer_pallas(
            x, s, n, bits=bits, stochastic=True, interpret=False),
        one_chip, ((per, w), jnp.float32), ((1, lay.n_blocks), jnp.float32),
        ((per, w), jnp.float32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("bits", (8, 4))
def test_dequant_mix_buffer_compiles_at_olmo_width(one_chip, bits):
    lay = _olmo_layout(bits)
    per, w, k = lay.per, lay.total_words, 3
    txt = _compiled_text(
        lambda x, q, s, wt: dequant_mix_buffer_pallas(
            x, q, s, wt, bits=bits, interpret=False),
        one_chip, ((per, w), jnp.float32), ((k, w), jnp.uint32),
        ((k, lay.n_blocks), jnp.float32), ((k,), jnp.float32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("bits", (8, 4))
def test_fused_round_encoder_compiles_at_olmo_width(one_chip, bits):
    lay = _olmo_layout(bits)
    buf = ((lay.per, lay.total_words), jnp.float32)
    txt = _compiled_text(
        lambda y, v, g, x, s, n, et: momentum_quantize_pack_buffer_pallas(
            y, v, g, x, s, n, et, bits=bits, stochastic=True,
            interpret=False),
        one_chip, buf, buf, buf, buf, ((1, lay.n_blocks), jnp.float32), buf,
        ((2,), jnp.float32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("bits", (8, 4))
def test_fused_round_decoder_compiles_at_olmo_width(one_chip, bits):
    lay = _olmo_layout(bits)
    w, k = lay.total_words, 3
    buf = ((lay.per, w), jnp.float32)
    txt = _compiled_text(
        lambda x, q, s, wt, v, g, et: dequant_mix_momentum_buffer_pallas(
            x, q, s, wt, v, g, et, bits=bits, interpret=False),
        one_chip, buf, ((k, w), jnp.uint32), ((k, lay.n_blocks), jnp.float32),
        ((k,), jnp.float32), buf, buf, ((2,), jnp.float32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16))
def test_momentum_sgd_compiles_at_olmo_width(one_chip, dtype):
    n = 2048 * 8192                     # one OLMo-1B MLP weight, flat
    txt = _compiled_text(
        lambda y, v, g: momentum_update_flat(y, v, g, 3e-2, 0.9,
                                             interpret=False),
        one_chip, ((n,), dtype), ((n,), dtype), ((n,), dtype))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("mode", ("eq5", "lemma5", "eq7"))
def test_dense_mixer_contracts_at_f32_precision(one_chip, mode):
    W = MixingSpec.ring(4, self_weight=0.5).W
    if mode == "eq5":
        fn = lambda x, z: mix_dense(W, {"w": z})
    else:
        q = QuantConfig(bits=8, stochastic=False, delta_mode=mode)
        fn = lambda x, z: _mix_dense_quantized(W, {"w": x}, {"w": z}, q,
                                               jax.random.PRNGKey(0))
    shape = ((4, 256, 512), jnp.float32)
    txt = _compiled_text(fn, one_chip, shape, shape)
    dots = [l for l in txt.splitlines()
            if " convolution(" in l or " dot(" in l]
    assert dots and all("operand_precision={highest,highest}" in l
                        for l in dots), dots


def test_block_sharded_q8_ring_compiles_for_four_chips(topo, monkeypatch):
    """Two clients per chip on a described 2x2 v5e: each chip's boundary
    lanes leave through ppermutes and land through the per-lane updates
    of ``core.mixing._put_lanes``; the codec runs as kernels. The leaf is
    one OLMo-1B MLP weight per client, in the config's bf16."""
    from repro.kernels import ops
    # The wire layout asks default_interpret(), which sees the CPU here.
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices[:4]), ("clients",))
    sched = TopologySchedule.constant(MixingSpec.ring(8, self_weight=0.5))
    q = QuantConfig(bits=8, stochastic=True, delta_mode="lemma5")
    mx = make_mixer(sched, MixerConfig(impl="sparse", quant=q, wire="planar"),
                    mesh=mesh, client_axes=("clients",))
    leaf = jax.ShapeDtypeStruct((8, 2048, 8192), jnp.bfloat16,
                                sharding=NamedSharding(mesh, P("clients")))
    txt = jax.jit(lambda x, z: mx({"w": x}, {"w": z}, jax.random.PRNGKey(0),
                                  0)[0]["w"]).lower(leaf, leaf
                                                    ).compile().as_text()
    assert "tpu_custom_call" in txt
    assert " collective-permute-start(" in txt or " collective-permute(" in txt
