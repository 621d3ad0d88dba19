"""Eq. 13 adjoint tests for the linear memory model (paper §2, App. A)."""

import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import adjoint_test
from repro.core import memory as mem

EPS = 1e-5


def _x(n, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (n,), jnp.float32)


class TestMemoryOps:
    def test_allocate_adjoint_is_deallocate(self):
        r = adjoint_test(lambda x: mem.allocate(x, 5), _x(7), name="allocate")
        assert r.passed, r

    def test_deallocate_adjoint_is_allocate(self):
        r = adjoint_test(lambda x: mem.deallocate(x, 3), _x(9), name="deallocate")
        assert r.passed, r

    def test_clear_self_adjoint(self):
        r = adjoint_test(lambda x: mem.clear(x, 2, 6), _x(8), name="clear")
        assert r.passed, r

    def test_add_adjoint_reverses_direction(self):
        f = lambda x: mem.add(x, (0, 3), (3, 6))
        r = adjoint_test(f, _x(6), name="add")
        assert r.passed, r
        # S*_{a->b} = S_{b->a} explicitly (paper Eq. 7)
        x = _x(6, 1)
        y = _x(6, 2)
        _, vjp = jax.vjp(f, x)
        (xbar,) = vjp(y)
        expected = mem.add(y, (3, 6), (0, 3))
        assert jnp.allclose(xbar, expected)

    def test_copy_inplace(self):
        r = adjoint_test(lambda x: mem.copy_inplace(x, (0, 4), (4, 8)), _x(8),
                         name="copy_inplace")
        assert r.passed, r

    def test_copy_outofplace(self):
        r = adjoint_test(lambda x: mem.copy_outofplace(x, (1, 4)), _x(6),
                         name="copy_outofplace")
        assert r.passed, r

    def test_move_inplace_adjoint_is_reverse_move(self):
        f = lambda x: mem.move_inplace(x, (0, 3), (3, 6))
        r = adjoint_test(f, _x(6), name="move_inplace")
        assert r.passed, r
        # M*_{a->b} = M_{b->a} (paper §2)
        x, y = _x(6, 3), _x(6, 4)
        _, vjp = jax.vjp(f, x)
        (xbar,) = vjp(y)
        assert jnp.allclose(xbar, mem.move_inplace(y, (3, 6), (0, 3)))

    def test_move_outofplace(self):
        r = adjoint_test(lambda x: mem.move_outofplace(x, (0, 2)), _x(5),
                         name="move_outofplace")
        assert r.passed, r

    def test_take_linear(self):
        r = adjoint_test(lambda x: mem.take_linear(x, (4, 1, 1, 0)), _x(5),
                         name="take_linear")
        assert r.passed, r


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(2, 64),
    data=st.data(),
    seed=st.integers(0, 2**16),
)
def test_memory_ops_adjoint_property(n, data, seed):
    """Property: every memory op passes Eq. 13 for arbitrary subset choices."""
    lo = data.draw(st.integers(0, n - 1))
    hi = data.draw(st.integers(lo + 1, n))
    x = _x(n, seed)
    assert adjoint_test(lambda v: mem.clear(v, lo, hi), x).passed
    assert adjoint_test(lambda v: mem.allocate(v, hi - lo), x).passed
    width = hi - lo
    if hi + width <= n:
        assert adjoint_test(lambda v: mem.add(v, (lo, hi), (hi, hi + width)), x).passed
        assert adjoint_test(lambda v: mem.copy_inplace(v, (lo, hi), (hi, hi + width)), x).passed
        assert adjoint_test(lambda v: mem.move_inplace(v, (lo, hi), (hi, hi + width)), x).passed


def test_forward_semantics():
    """The operators do what the paper says they do."""
    x = jnp.arange(1.0, 7.0)
    assert jnp.allclose(mem.allocate(x, 2), jnp.array([1, 2, 3, 4, 5, 6, 0, 0.]))
    assert jnp.allclose(mem.clear(x, 0, 2), jnp.array([0, 0, 3, 4, 5, 6.]))
    assert jnp.allclose(mem.add(x, (0, 2), (2, 4)), jnp.array([1, 2, 4, 6, 5, 6.]))
    assert jnp.allclose(mem.copy_inplace(x, (0, 2), (2, 4)), jnp.array([1, 2, 1, 2, 5, 6.]))
    assert jnp.allclose(mem.move_inplace(x, (0, 2), (2, 4)), jnp.array([0, 0, 1, 2, 5, 6.]))
    assert jnp.allclose(mem.copy_outofplace(x, (1, 3)), jnp.array([1, 2, 3, 4, 5, 6, 2, 3.]))
