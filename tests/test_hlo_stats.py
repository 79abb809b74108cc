"""HLO collective parser + jaxpr structural cost model."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.cost_model import structural_costs
from repro.launch.hlo_stats import (_group_size, _shape_bytes,
                                    collect_collectives,
                                    collect_collectives_looped)


def test_shape_bytes():
    assert _shape_bytes("bf16", "16,1024") == 16 * 1024 * 2
    assert _shape_bytes("f32", "8") == 32
    assert _shape_bytes("u32", "") == 4          # scalar


def test_group_size_formats():
    assert _group_size("... replica_groups={{0,1,2,3},{4,5,6,7}} ...") == 4
    assert _group_size("... replica_groups=[2,128]<=[256] ...") == 128
    assert _group_size("... source_target_pairs={{0,1},{1,0}} ...") == 2


SAMPLE = """
HloModule test

%cond.1 (arg: (s32[], f32[8])) -> pred[] {
  %arg = (s32[], f32[8]) parameter(0)
  %gte = s32[] get-tuple-element(%arg), index=0
  %constant.5 = s32[] constant(30)
  ROOT %cmp = pred[] compare(%gte, %constant.5), direction=LT
}

%body.1 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]) parameter(0)
  %x = f32[8]{0} get-tuple-element(%arg), index=1
  %ar = f32[8]{0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%sum
  ROOT %t = (s32[], f32[8]) tuple(%gte, %ar)
}

ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %ag = f32[32]{0} all-gather(%p0), replica_groups={{0,1,2,3}}, dimensions={0}
  %w = (s32[], f32[8]) while(%init), condition=%cond.1, body=%body.1
  ROOT %out = f32[8]{0} get-tuple-element(%w), index=1
}
"""


def test_flat_vs_looped_counting():
    flat = collect_collectives(SAMPLE)
    looped = collect_collectives_looped(SAMPLE)
    # flat: 1 all-gather (32 f32 * 3/4 = 96B) + 1 all-reduce (2*32*(3/4)=48B)
    assert flat.counts["all-gather"] == 1
    assert flat.counts["all-reduce"] == 1
    assert flat.by_kind["all-gather"] == 32 * 4 * 3 / 4
    # looped: the all-reduce sits in a while body with trip count 30
    assert looped.counts["all-reduce"] == 30
    assert looped.by_kind["all-reduce"] == 30 * 2 * 32 * 3 / 4
    assert looped.counts["all-gather"] == 1


def test_structural_costs_scan_multiplier():
    def f(x):
        def body(c, _):
            return c @ x, None
        out, _ = jax.lax.scan(body, jnp.eye(16), None, length=10)
        return out

    x = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    costs = structural_costs(f, x)
    # 10 iterations x 2*16^3 flops
    assert abs(costs.flops - 10 * 2 * 16 ** 3) / (10 * 2 * 16 ** 3) < 0.2


def test_structural_costs_counts_grad_and_remat():
    def loss(w, x):
        def block(h, wi):
            return jnp.tanh(h @ wi), None
        h, _ = jax.lax.scan(block, x, w)
        return jnp.sum(h ** 2)

    w = jax.ShapeDtypeStruct((6, 32, 32), jnp.float32)
    x = jax.ShapeDtypeStruct((32,), jnp.float32)
    c_fwd = structural_costs(loss, w, x)
    c_grad = structural_costs(jax.grad(loss), w, x)
    assert c_grad.flops > 2 * c_fwd.flops        # bwd ~ 2x fwd matmuls


def test_structural_costs_collectives():
    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh((1,), ("x",))

    def f(a):
        return jax.shard_map(
            lambda v: jax.lax.psum(v, "x"), mesh=mesh,
            in_specs=jax.sharding.PartitionSpec("x"),
            out_specs=jax.sharding.PartitionSpec())(a)

    a = jax.ShapeDtypeStruct((8,), jnp.float32)
    costs = structural_costs(f, a)
    assert costs.coll_bytes == 2 * 8 * 4         # psum = 2x operand
    assert "all-reduce" in costs.coll_by_kind
