"""Mamba2 SSD chunk scan — Pallas TPU kernel.

TPU adaptation: one grid cell per (batch, head, chunk); the SSM state
(head_dim x d_state) lives in VMEM scratch and persists across the chunk
dimension (innermost grid axis, sequential on TPU).  The within-chunk
quadratic term is an (L x L) fp32 MXU matmul — the "duality" form — and the
cross-chunk recurrence costs one rank-N update per chunk, so HBM traffic is
O(S·(P+N)) instead of the O(S·P·N) a naive recurrence would stream.

Semantics (h_{-1} = 0):
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t ;   y_t = C_t · h_t
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_scr, *, L: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0, :, 0, :].astype(jnp.float32)          # (L, P)
    dt = dt_ref[0, 0, :, 0].astype(jnp.float32)           # (L,)
    a_neg = a_ref[0].astype(jnp.float32)                  # ()
    bm = b_ref[0, 0].astype(jnp.float32)                  # (L, N)
    cm = c_ref[0, 0].astype(jnp.float32)                  # (L, N)

    a = dt * a_neg                                        # (L,) <= 0
    acum = jnp.cumsum(a)
    seg = acum[:, None] - acum[None, :]                   # (L, L)
    causal = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    # mask before exp: anti-causal seg >> 0 would overflow to inf
    w = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.dot(cm, bm.T, preferred_element_type=jnp.float32)
    wmat = cb * w * dt[None, :]
    y_intra = jnp.dot(wmat, x, preferred_element_type=jnp.float32)

    h = h_scr[...]                                        # (P, N)
    y_inter = jnp.dot(cm, h.T, preferred_element_type=jnp.float32) \
        * jnp.exp(acum)[:, None]                          # (L, P)

    decay_end = jnp.exp(acum[-1] - acum)                  # (L,)
    s_c = jnp.dot(x.T, bm * (dt * decay_end)[:, None],
                  preferred_element_type=jnp.float32)     # (P, N)
    h_scr[...] = h * jnp.exp(acum[-1]) + s_c

    y_ref[0, 0, :, 0, :] = (y_intra + y_inter).astype(y_ref.dtype)


def ssd_scan_fwd(x, dt, a_neg, Bm, Cm, *, chunk=64, interpret=False):
    """x: (B,S,H,P); dt: (B,S,H); a_neg: (H,); Bm/Cm: (B,S,N) -> y (B,S,H,P)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    L = min(chunk, S)
    assert S % L == 0
    nc = S // L
    xr = x.reshape(B, nc, L, H, P)
    dtr = dt.reshape(B, nc, L, H)
    br = Bm.reshape(B, nc, L, N)
    cr = Cm.reshape(B, nc, L, N)

    kernel = functools.partial(_ssd_kernel, L=L)
    y = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, L, 1, P), lambda b, h, j: (b, j, 0, h, 0)),
            pl.BlockSpec((1, 1, L, 1), lambda b, h, j: (b, j, 0, h)),
            pl.BlockSpec((1,), lambda b, h, j: (h,)),
            pl.BlockSpec((1, 1, L, N), lambda b, h, j: (b, j, 0, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, h, j: (b, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, L, 1, P), lambda b, h, j: (b, j, 0, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nc, L, H, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xr, dtr, a_neg, br, cr)
    return y.reshape(B, S, H, P)
