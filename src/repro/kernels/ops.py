"""Jit'd dispatch wrappers over the Pallas kernels.

``impl`` selects the implementation:
  "xla"              pure-jnp path (the reference the kernels are tested
                     against)
  "pallas"           compiled Pallas kernel (TPU target)
  "pallas_interpret" Pallas kernel body executed in Python (CPU validation)
  None               chosen by platform: "pallas" on a TPU backend,
                     "pallas_interpret" anywhere else

Training uses custom_vjp wrappers whose backward recomputes through the
(differentiable) XLA oracle — the two implementations compute the same
function, so mixing them across fwd/bwd is exact up to numerics, and the
kernel sweeps in tests/test_kernels.py pin that equivalence.
"""

from __future__ import annotations

from functools import partial

import jax

from . import ref
from .flash_attention import flash_attention_fwd
from .rmsnorm import rmsnorm_fwd
from .ssd_scan import ssd_scan_fwd

def _resolve(impl):
    if impl is not None:
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "pallas_interpret"


# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal=True, impl=None):
    impl = _resolve(impl)
    if impl == "xla":
        from repro.models.attention import blockwise_attention
        return blockwise_attention(q, k, v, chunk=min(512, k.shape[1]),
                                   causal=causal)
    return flash_attention_fwd(q, k, v, causal=causal,
                               interpret=(impl == "pallas_interpret"))


def _fa_fwd(q, k, v, causal, impl):
    return flash_attention(q, k, v, causal, impl), (q, k, v)


def _fa_bwd(causal, impl, res, g):
    from repro.models.attention import blockwise_attention
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: blockwise_attention(q, k, v,
                                            chunk=min(512, k.shape[1]),
                                            causal=causal), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def ssd_scan(x, dt, a_neg, Bm, Cm, chunk=64, impl=None):
    impl = _resolve(impl)
    if impl == "xla":
        from repro.models.ssm import ssd_chunked
        y, _ = ssd_chunked(x, dt, a_neg, Bm, Cm, chunk=chunk)
        return y
    return ssd_scan_fwd(x, dt, a_neg, Bm, Cm, chunk=chunk,
                        interpret=(impl == "pallas_interpret"))


def _ssd_fwd(x, dt, a_neg, Bm, Cm, chunk, impl):
    return ssd_scan(x, dt, a_neg, Bm, Cm, chunk, impl), (x, dt, a_neg, Bm, Cm)


def _ssd_bwd(chunk, impl, res, g):
    from repro.models.ssm import ssd_chunked
    x, dt, a_neg, Bm, Cm = res
    _, vjp = jax.vjp(
        lambda *a: ssd_chunked(*a, chunk=chunk)[0], x, dt, a_neg, Bm, Cm)
    return vjp(g)


ssd_scan.defvjp(_ssd_fwd, _ssd_bwd)


# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def rmsnorm(x, w, eps=1e-6, impl=None):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.rmsnorm_ref(x, w, eps)
    return rmsnorm_fwd(x, w, eps=eps, interpret=(impl == "pallas_interpret"))


def _rms_fwd(x, w, eps, impl):
    return rmsnorm(x, w, eps, impl), (x, w)


def _rms_bwd(eps, impl, res, g):
    x, w = res
    _, vjp = jax.vjp(lambda x, w: ref.rmsnorm_ref(x, w, eps), x, w)
    return vjp(g)


rmsnorm.defvjp(_rms_fwd, _rms_bwd)
