"""Fused RMSNorm — Pallas TPU kernel.

Row-tiled: each grid cell normalizes a (block_rows x d) tile in one VMEM
round-trip (read x, write y), fusing the mean-square reduction, rsqrt and
scale that XLA otherwise materializes through HBM twice.  fp32 internals.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * w_ref[...].astype(jnp.float32)[None, :]).astype(o_ref.dtype)


def rmsnorm_fwd(x, w, *, eps=1e-6, block_rows=128, interpret=False):
    """x: (..., d); w: (d,).

    The row block is either every row or ``block_rows``, a multiple of the
    TPU's 8-row sublane tiling; a row count it does not divide leaves a
    partial last block, whose out-of-range rows are normalized on their
    own (rows never mix) and never written back.
    """
    if block_rows % 8:
        raise ValueError(f"block_rows={block_rows} is not a multiple of 8")
    orig_shape = x.shape
    d = x.shape[-1]
    rows = int(x.size // d)
    xr = x.reshape(rows, d)
    br = min(rows, block_rows)
    kernel = functools.partial(_rmsnorm_kernel, eps=eps)
    y = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, br),),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
    )(xr, w)
    return y.reshape(orig_shape)
