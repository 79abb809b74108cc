"""The chunked SSD scan has a finite gradient on chunks whose decay is
large: above the diagonal of a chunk's segment sums the differences of
the cumulative ``dt * A`` are positive, and once a chunk's sum passes
float32's ``exp`` range they must be masked before ``exp``, or the
backward pass multiplies an inf by a zero cotangent."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.models.ssm import _segsum_decay, ssd_chunked


def _inputs(l=40, h=2, p=4, n=3, da=-6.0):
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k[0], (1, l, h, p))
    B = jax.random.normal(k[1], (1, l, n))
    C = jax.random.normal(k[2], (1, l, n))
    # Every step decays by da: a chunk of 20 sums to 20 * da = -120,
    # below float32's exp range (about -88) and past it above the
    # diagonal (about +88).
    dA = jnp.full((1, l, h), da)
    return x, dA, B, C


def test_ssd_gradient_is_finite_when_a_chunk_decays_below_minus_100():
    x, dA, B, C = _inputs()

    def loss(x, dA, B, C):
        y, s = ssd_chunked(x, dA, B, C, chunk=20)
        return jnp.sum(y ** 2) + jnp.sum(s ** 2)

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(x, dA, B, C)
    for g in grads:
        assert bool(jnp.isfinite(g).all())


def test_segsum_decay_values_are_unchanged_by_the_mask():
    # Where the unmasked form was finite it gives the same bits.
    cs = jnp.cumsum(jnp.linspace(-0.5, -0.1, 16))
    diff = cs[:, None] - cs[None, :]
    want = jnp.where(jnp.tril(jnp.ones((16, 16), bool)), jnp.exp(diff), 0.0)
    np.testing.assert_array_equal(np.asarray(_segsum_decay(cs)),
                                  np.asarray(want))
