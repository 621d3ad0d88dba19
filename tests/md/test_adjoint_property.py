"""Property-based adjoint fuzzer: Eq. 13 and the reversal law for RANDOM
operator chains, not a hand-picked list.

Each example draws a mesh-axis choice, a starting shape, and a chain of
1-5 ``LinearOp``s whose boundary *spaces* compose (the paper's operators
are maps between specific global vector spaces — replicated F^n vs
k-worker-stacked F^{kn}).  The generator samples from the SHARED space
registry ``repro.analysis.spaces`` (``legal_moves``/``apply_move`` driven
by each op's own ``space_map``) instead of a hand-rolled tracker, so the
fuzzer and the static typechecker can never drift; each sampled chain is
additionally run through ``typecheck`` before touching a device.  Asserts:

  - ``typecheck``: every sampled chain is statically well-typed;
  - ``check_adjoint``: <Ax, y> == <x, A*y> under the lifted global
    operators AND jax.vjp coherence (paper Eq. 13), on real devices;
  - the §2 reversal law ``(A @ B).T == B.T @ A.T``, structurally.

Runs on whatever host devices exist: with 8 devices it fuzzes 1-D/2-D/3-D
meshes (axis sizes 8, 2, 4); with 1 device every axis degenerates to size
1 and the algebra must still hold (the CI device-count matrix covers both).
"""

import jax
import pytest
from hypothesis import HealthCheck, given, settings, strategies as st

from repro import compat
from repro.analysis import spaces
from repro.core import linop
from repro.core.linop import Space, check_adjoint

# ~170 s on 8 emulated devices: the CI slow leg runs it, not tier-1
# (tests/md/test_linop.py keeps every op and composite there)
pytestmark = pytest.mark.slow

MAX_DIM = 256          # cap local growth (all_gather/grad_sum_reduce x k)
N_EXAMPLES = 60        # >= 50 random composites per CI run


def _axis_choices():
    """(mesh, axis, k) triples over however many host devices exist."""
    n = len(jax.devices())
    choices = [(compat.make_mesh((n,), ("ax0",)), "ax0", n)]
    if n >= 8:
        m2 = compat.make_mesh((2, 4), ("d0", "d1"))
        m3 = compat.make_mesh((2, 2, 2), ("data", "pipe", "model"))
        m4 = compat.make_mesh((2, 1, 2, 2), ("data", "pipe", "ctx", "model"))
        m5 = compat.make_mesh((2, 1, 1, 2, 2),
                              ("data", "pipe", "ctx", "model", "ep"))
        choices += [(m2, "d0", 2), (m2, "d1", 4),
                    (m3, "data", 2), (m3, "pipe", 2), (m3, "model", 2),
                    (m4, "ctx", 2), (m4, "model", 2),
                    (m5, "ep", 2), (m5, "data", 2)]
    return choices


_CHOICES = _axis_choices()


def _draw_chain(data, ax, k):
    """A space-typed random chain sampled from the SHARED registry
    (repro.analysis.spaces): (ops in application order, start Space)."""
    rank = data.draw(st.integers(2, 3))
    if data.draw(st.integers(0, 1)):
        sig = data.draw(st.integers(0, rank - 1))
        ls = [data.draw(st.integers(1, 4)) for _ in range(rank)]
        space = Space.stacked(ax, sig, ls)
    else:
        # replicated start: dims are multiples of k so BatchScatter is live
        space = Space.replicated(
            [k * data.draw(st.integers(1, 2)) for _ in range(rank)])
    space0 = space
    n_ops = data.draw(st.integers(1, 5))
    ops = []
    for _ in range(n_ops):
        mv = spaces.legal_moves(ax, k, space, max_dim=MAX_DIM)
        if not mv:
            break
        op, space = spaces.apply_move(ax, k, space,
                                      data.draw(st.sampled_from(mv)))
        ops.append(op)
    return ops, space0


@settings(max_examples=N_EXAMPLES, deadline=None,
          suppress_health_check=list(HealthCheck))
@given(data=st.data())
def test_random_composites_pass_eq13_and_reversal(data):
    mesh, ax, k = _CHOICES[data.draw(st.integers(0, len(_CHOICES) - 1))]
    ops, space0 = _draw_chain(data, ax, k)
    chain = ops[0]
    for op in ops[1:]:
        chain = op @ chain
    # The static judgment accepts every sampled chain (generator and
    # typechecker share one registry, so this can only fail if the chain
    # builder itself drifts).
    spaces.typecheck(chain, {ax: k}, space0)
    gshape = space0.global_shape(k)
    # Eq. 13 on real devices, for the composite AND (implicitly) every
    # custom-vjp rule inside it.
    r = check_adjoint(chain, mesh, gshape,
                      name=f"fuzz[{ax}x{k}]{[type(o).__name__ for o in ops]}")
    assert r.passed, r
    # §2 reversal law, structurally, plus involution: ``ops`` is in
    # APPLICATION order, so the adjoint chain applies the adjoints in the
    # opposite order — matrix order (first-applied op's adjoint outermost-
    # last) is exactly ``ops`` order again.
    if isinstance(chain, linop.Compose):
        assert chain.T == linop.Compose(tuple(o.T for o in ops))
    else:
        assert chain.T == ops[0].T
    assert chain.T.T == chain


def test_new_dp_pair_in_adjoint_registry():
    """The DP pair is registered centrally like every other op (structural
    — axis strings are opaque to frozen-dataclass equality, so one axis
    name covers all meshes; device-backed coverage is the fuzzer above)."""
    ax = "data"
    assert linop.BatchScatter(ax, 1).T == linop.GradSumReduce(ax, 1)
    assert linop.GradSumReduce(ax, 1).T == linop.BatchScatter(ax, 1)
    assert linop.BatchScatter(ax, 0).T.T == linop.BatchScatter(ax, 0)
