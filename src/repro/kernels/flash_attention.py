"""Flash attention forward — Pallas TPU kernel.

TPU adaptation (not a CUDA port): the online-softmax accumulator lives in
VMEM scratch that persists across the innermost (KV) grid dimension; the
(bq x bk) score tile feeds the MXU as an fp32 matmul with 128-aligned tile
dims.  Causality is exploited by *skipping* fully-masked KV blocks via
pl.when on the block predicate — this is the 2x FLOP saving the XLA
blockwise path cannot express (it must mask, not skip), and is the reason
attention compute halves when this kernel replaces the XLA path on TPU
(see EXPERIMENTS.md §Perf).

Grid: (B * KH * group, nq, nk), sequential in nk (TPU grid semantics:
last dim innermost), scratch carries (m, l, acc) per (bh, iq).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, bq: int, bk: int, nk: int):
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal block skip: KV block strictly above the diagonal touches no
    # valid (q, k) pair -> skip the whole tile (compute saving, not a mask).
    run = (jk * bk <= iq * bq + bq - 1) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # (bq, hd)
        k = k_ref[0].astype(jnp.float32)              # (bk, hd)
        v = v_ref[0].astype(jnp.float32)              # (bk, hd)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(jk == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal=True, bq=128, bk=128,
                        interpret=False):
    """q: (B, Sq, H, hd); k/v: (B, Skv, KH, hd).  GQA via head replication
    of KV *indices* (no materialized repeat: the BlockSpec index map points
    group-mates at the same KV block)."""
    B, Sq, H, hd = q.shape
    Skv, KH = k.shape[1], k.shape[2]
    group = H // KH
    scale = 1.0 / np.sqrt(hd)
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    nq, nk = Sq // bq, Skv // bk

    qh = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(B * KH, Skv, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(B * KH, Skv, hd)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            # GQA: head b of Q reads KV head b // group.
            pl.BlockSpec((1, bk, hd), lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j, g=group: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max m
            pltpu.VMEM((bq, 1), jnp.float32),    # running denom l
            pltpu.VMEM((bq, hd), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(qh, kh, vh)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
