"""Ring collective-matmul overlap vs unfused reference (values and grads)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import overlap, primitives as prim

# ~100 s on 8 emulated devices: the CI slow leg runs it, not tier-1
pytestmark = pytest.mark.slow


def _r(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def test_ring_allgather_matmul_matches_unfused(mesh1d):
    # x sharded on features; w holds all rows, cols sharded.
    x = _r((4, 32), 0)
    w = _r((32, 24), 1)

    ring = prim.smap(
        lambda x, w: overlap.ring_allgather_matmul(x, w, "model"),
        mesh1d, (P(None, "model"), P(None, "model")), P(None, "model"))
    unfused = prim.smap(
        lambda x, w: prim.all_gather(x, "model", 1) @ w,
        mesh1d, (P(None, "model"), P(None, "model")), P(None, "model"))

    np.testing.assert_allclose(ring(x, w), unfused(x, w), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(ring(x, w), x @ w, rtol=2e-5, atol=2e-5)

    g_ring = jax.grad(lambda w: (ring(x, w) ** 2).sum())(w)
    g_ref = jax.grad(lambda w: ((x @ w) ** 2).sum())(w)
    np.testing.assert_allclose(g_ring, g_ref, rtol=1e-4, atol=1e-4)


def test_ring_matmul_reducescatter_matches_unfused(mesh1d):
    x = _r((4, 32), 2)
    w = _r((32, 24), 3)

    ring = prim.smap(
        lambda x, w: overlap.ring_matmul_reducescatter(x, w, "model"),
        mesh1d, (P(None, "model"), P("model", None)), P(None, "model"))
    np.testing.assert_allclose(ring(x, w), x @ w, rtol=2e-5, atol=2e-5)

    gx_ring = jax.grad(lambda x: (ring(x, w) ** 2).sum())(x)
    gx_ref = jax.grad(lambda x: ((x @ w) ** 2).sum())(x)
    np.testing.assert_allclose(gx_ring, gx_ref, rtol=1e-4, atol=1e-4)
