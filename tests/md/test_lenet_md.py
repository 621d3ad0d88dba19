"""Distributed LeNet-5 (paper §5) vs sequential, on a real 2x2 mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat

from repro.models.lenet import (lenet_apply_distributed,
                                lenet_apply_sequential, lenet_init,
                                synthetic_mnist, table1_local_shapes)

# ~230 s on 8 emulated devices: the CI slow leg runs it, not tier-1
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def mesh22():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    return compat.make_mesh((2, 2), ("fo", "fi"))


def test_forward_matches_sequential(mesh22):
    params = lenet_init(jax.random.PRNGKey(0))
    x, _ = synthetic_mnist(jax.random.PRNGKey(1), 8)
    ld = lenet_apply_distributed(mesh22, params, x)
    ls = lenet_apply_sequential(params, x)
    np.testing.assert_allclose(np.asarray(ld), np.asarray(ls),
                               atol=2e-4, rtol=2e-4)


def test_gradients_match_sequential(mesh22):
    params = lenet_init(jax.random.PRNGKey(2))
    x, y = synthetic_mnist(jax.random.PRNGKey(3), 8)

    def xent(logits):
        return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(8), y])

    gd = jax.grad(lambda p: xent(lenet_apply_distributed(mesh22, p, x)))(params)
    gs = jax.grad(lambda p: xent(lenet_apply_sequential(p, x)))(params)
    for (ka, a), (kb, b) in zip(
            sorted(jax.tree_util.tree_leaves_with_path(gd),
                   key=lambda t: str(t[0])),
            sorted(jax.tree_util.tree_leaves_with_path(gs),
                   key=lambda t: str(t[0]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3,
                                   err_msg=str(ka))


def test_table1_shapes(mesh22):
    # paper Table 1: per-worker affine weights on the 2x2 partition
    t = table1_local_shapes((2, 2))
    assert t == {"C5": (60, 200), "F6": (42, 60), "Output": (5, 42)}


def test_short_training_equivalence(mesh22):
    """Five SGD steps: distributed and sequential losses coincide (the
    paper's §5 equivalence, abbreviated)."""
    params_d = lenet_init(jax.random.PRNGKey(4))
    params_s = jax.tree_util.tree_map(jnp.copy, params_d)
    x, y = synthetic_mnist(jax.random.PRNGKey(5), 32)

    def xent(logits):
        return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(32), y])

    for _ in range(5):
        ld, gd = jax.value_and_grad(
            lambda p: xent(lenet_apply_distributed(mesh22, p, x)))(params_d)
        ls, gs = jax.value_and_grad(
            lambda p: xent(lenet_apply_sequential(p, x)))(params_s)
        assert abs(float(ld) - float(ls)) < 1e-3, (float(ld), float(ls))
        params_d = jax.tree_util.tree_map(lambda p, g: p - 0.05 * g, params_d, gd)
        params_s = jax.tree_util.tree_map(lambda p, g: p - 0.05 * g, params_s, gs)
