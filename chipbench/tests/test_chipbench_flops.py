"""The benchmark's counts of work, checked against the program's own
parameter count and wire layout (CPU)."""
import dataclasses

import jax
import pytest

import flops
import spec
from repro.configs import get_config, reduced
from repro.core.wire_layout import WireLayout
from repro.models import model as M


def _model(arch_cfg) -> dict:
    keys = ("n_layers", "d_model", "n_heads", "head_dim", "d_ff",
            "vocab_size", "ssm_state", "ssm_expand", "ssm_head_dim",
            "dtype")
    return {k: getattr(arch_cfg, k) for k in keys}


def _non_matmul_params(c) -> int:
    """Norm scales, convolutions and the state-space scalars: the
    parameters ``ArchConfig.n_params`` counts that no matmul uses."""
    d, L = c.d_model, c.n_layers
    if c.ssm_state:
        di = c.ssm_expand * d
        h = di // c.ssm_head_dim
        per = 4 * (di + 2 * c.ssm_state) + 3 * h + di + d
    else:
        per = 2 * d
    return L * per + d


@pytest.mark.parametrize("arch", ["olmo-1b", "mamba2-780m"])
@pytest.mark.parametrize("layers", [2, 4])
def test_matmul_params_are_n_params_less_non_matmul(arch, layers):
    c = dataclasses.replace(get_config(arch), n_layers=layers)
    assert flops.matmul_params(_model(c)) == \
        c.n_params() - _non_matmul_params(c)


def test_olmo_flops_per_token_at_published_widths():
    c = spec.Cell(spec.load(), "olmo1b.k8.q8")
    per_token = flops.train_flops_per_token(c.model, 1024)
    six_n = 6 * flops.matmul_params(c.model)
    attn = 3 * 2 * 2 * 2 * 16 * 128 * 1025 / 2
    assert per_token == pytest.approx(six_n + attn)
    assert 1.4e9 < per_token < 1.6e9


@pytest.mark.parametrize("arch", ["olmo-1b", "mamba2-780m"])
def test_leaf_sizes_match_wire_layout(arch):
    c = reduced(get_config(arch))
    shapes = jax.eval_shape(lambda k: M.init_model(k, c)[0],
                            jax.random.PRNGKey(0))
    lay = WireLayout.for_tree(shapes, bits=8)
    assert flops.leaf_sizes(_model(c)) == list(lay.sizes)


@pytest.mark.parametrize("arch", ["olmo-1b", "mamba2-780m"])
@pytest.mark.parametrize("bits", [8, 4])
def test_codec_least_bytes_from_layout_shapes(arch, bits):
    c = reduced(get_config(arch))
    shapes = jax.eval_shape(lambda k: M.init_model(k, c)[0],
                            jax.random.PRNGKey(0))
    lay = WireLayout.for_tree(shapes, bits=bits)
    n = sum(lay.sizes)
    param_bytes = sum(s * dt.itemsize for s, dt in zip(lay.sizes,
                                                       lay.dtypes))
    packed = n * bits / 8 + 4 * lay.n_leaves
    want = (4 * n + packed) + (2 * packed + 2 * param_bytes)
    got = flops.codec_least_bytes(_model(c), bits, streams=2)
    assert got == pytest.approx(want)
