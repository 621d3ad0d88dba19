"""GQA attention: blockwise-causal for train/prefill, cached for decode.

The train/prefill path is a pure-XLA blockwise (online-softmax) attention —
memory O(chunk * S) instead of O(S^2) — differentiable (scan over all KV
blocks with masking).  The Pallas flash kernel (kernels/flash_attention.py)
is the TPU-target replacement for the same contraction; on the CPU dry-run
backend this XLA path is what lowers.

Decode uses a single-token contraction against the KV cache; the cache's
head_dim is sharded over the model axis (sharding/policy.py "kvdim"), so
the score contraction produces psum-combined partials — the paper's
sum-reduce of linear partials (flash-decoding's combine).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import apply_rope, dense_init

NEG_INF = -1e30


def attn_init(key, cfg, dtype) -> dict:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, d, cfg.num_heads * hd, dtype),
        "wk": dense_init(kk, d, cfg.num_kv_heads * hd, dtype),
        "wv": dense_init(kv, d, cfg.num_kv_heads * hd, dtype),
        "wo": dense_init(ko, cfg.num_heads * hd, d, dtype),
    }


def _split_heads(x, n_heads, hd):
    return x.reshape(x.shape[:-1] + (n_heads, hd))


def blockwise_attention(q, k, v, *, chunk: int, causal: bool = True,
                        unroll: bool = False):
    """Online-softmax attention over KV chunks.

    q: (B, Sq, H, hd); k, v: (B, Skv, KH, hd) with H % KH == 0.
    Returns (B, Sq, H, hd).  fp32 accumulation.
    """
    B, Sq, H, hd = q.shape
    Skv, KH = k.shape[1], k.shape[2]
    group = H // KH
    scale = 1.0 / np.sqrt(hd)

    # GQA via explicit KV head repeat: a (B,S,KH,group,hd) grouped layout
    # shards catastrophically under GSPMD when KH < mesh model size (the
    # partitioner replicates the whole attention — measured in §Perf v0);
    # repeating KV to H heads keeps every tensor sharded on the plain heads
    # dim.  XLA fuses the repeat (it is a broadcast), so no HBM cost on the
    # repeated operand itself.
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)

    chunk = min(chunk, Skv)
    pad = (-Skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nkv = (Skv + pad) // chunk
    # keep operands in input dtype; accumulate in fp32 via the MXU-style
    # preferred_element_type (no fp32 materialization of K/V).
    kc_all = k.reshape(B, nkv, chunk, H, hd)
    vc_all = v.reshape(B, nkv, chunk, H, hd)
    q_pos = jnp.arange(Sq)

    def step(carry, inputs):
        m, l, acc = carry
        kc, vc, j = inputs
        s = jnp.einsum("bqhd,bchd->bqhc", q, kc,
                       preferred_element_type=jnp.float32) * scale
        kv_pos = j * chunk + jnp.arange(chunk)
        mask = kv_pos[None, :] < Skv                           # padding mask
        if causal:
            mask = mask & (q_pos[:, None] >= kv_pos[None, :])  # (Sq, chunk)
        s = jnp.where(mask[None, :, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bqhc,bchd->bqhd", p.astype(q.dtype), vc,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, Sq, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, H), jnp.float32)
    acc0 = jnp.zeros((B, Sq, H, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0),
        (kc_all.swapaxes(0, 1), vc_all.swapaxes(0, 1), jnp.arange(nkv)),
        unroll=unroll)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, k_new, v_new):
    """Single-token attention against a cache and the token's own k/v.

    q: (B, 1, H, hd); caches: (B, KH, S_max, hd), the order the two
    contractions read, with slots ``< cache_len`` valid (cache_len: () or
    (B,)); k_new, v_new: (B, KH, 1, hd), the token at slot ``cache_len``.
    One softmax over the cached slots and the new token, fp32 throughout:
    the cache is only read here (the caller writes the new token).
    """
    B, _, H, hd = q.shape
    KH, S = k_cache.shape[1], k_cache.shape[2]
    group = H // KH
    scale = 1.0 / np.sqrt(hd)
    # Contract per KV head with the query group folded into the head dim:
    # no fp32 materialization of the cache (einsum accumulates fp32), no
    # grouped reshape of sharded dims.
    qf = q.reshape(B, KH, group, hd)
    s = jnp.einsum("bkgh,bksh->bkgs", qf, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s_new = jnp.einsum("bkgh,bksh->bkgs", qf, k_new,
                       preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(S)
    valid = pos[None, :] < jnp.reshape(cache_len, (-1, 1))     # (B or 1, S)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.maximum(s.max(axis=-1, keepdims=True), s_new)
    p = jnp.exp(s - m)
    p_new = jnp.exp(s_new - m)
    l = p.sum(axis=-1, keepdims=True) + p_new
    out = (jnp.einsum("bkgs,bksh->bkgh", (p / l).astype(q.dtype), v_cache,
                      preferred_element_type=jnp.float32)
           + jnp.einsum("bkgs,bksh->bkgh", (p_new / l).astype(q.dtype), v_new,
                        preferred_element_type=jnp.float32))
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def attention_block_tp(p, h, cfg, policy, *, positions):
    """Explicit-TP attention sub-layer on LOCAL shards (inside dist_jit).

    h: (B_loc, S_loc, d_model/tp) — the residual stream is FEATURE-sharded
    over the model axis, so the qkv projections are gather-affines (paper's
    partitioned broadcast B fused with the GEMM as a ring collective-matmul
    when policy.explicit_tp) and the output projection is a scatter-affine
    (GEMM fused with the adjoint reduce-scatter R).  Heads stay sharded in
    between; attention itself is head-local — UNLESS the policy carries a
    live ctx axis, in which case S_loc is a sequence shard and the score
    contraction runs the KVRingShift ring (core/ring_attention.py): the
    ctx and model axes compose inside one region, ring collective-matmuls
    on ``model`` around ring attention on ``ctx``.  ``positions`` must
    then carry GLOBAL positions (the caller offsets by the ctx rank).
    Train/prefill math only (no cache plumbing here).
    """
    from repro.core import layers as L
    from repro.core.ring_attention import ring_attention

    ax = policy.model_axis
    tp = policy.model_size
    hd = cfg.resolved_head_dim
    q = _split_heads(L.affine_gather(h, p["wq"], axis=ax), cfg.num_heads // tp, hd)
    k = _split_heads(L.affine_gather(h, p["wk"], axis=ax), cfg.num_kv_heads // tp, hd)
    v = _split_heads(L.affine_gather(h, p["wv"], axis=ax), cfg.num_kv_heads // tp, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    ctx = policy.active_ctx_axis
    if ctx is not None:
        out = ring_attention(q, k, v, ctx, chunk=cfg.attn_chunk,
                             unroll=cfg.unroll_scans)
    else:
        out = blockwise_attention(q, k, v, chunk=cfg.attn_chunk,
                                  unroll=cfg.unroll_scans)
    out = out.reshape(out.shape[0], out.shape[1], (cfg.num_heads // tp) * hd)
    return L.affine_scatter(out, p["wo"], axis=ax)


def attention_block(p, x, cfg, policy, *, positions, mode, cache=None,
                    cache_len=None, use_flash: bool = False, ctx_axis=None):
    """Full attention sub-layer: qkv proj -> rope -> attend -> out proj.

    x: (B, S, d).  Returns (out, new_cache).
    In train/prefill ``cache`` is None / being built (prefill returns the
    prompt's k/v as (B, KH, S, hd)); in decode S == 1, ``cache`` is one
    layer's (B, KH, S_max, hd) k/v, only read, and ``new_cache`` is the
    new token's (B, KH, 1, hd) k/v for the caller to write at ``cache_len``.
    TP: heads sharded over the model axis (the paper's affine P_fo); under
    SP the incoming residual is seq-sharded and GSPMD inserts the
    seq->heads repartition (the paper's generalized all-to-all) — UNLESS
    context parallelism is live (``policy.active_ctx_axis``), in which
    case the train path keeps q/k/v sequence-sharded and dispatches to the
    KVRingShift ring (``core/ring_attention.py``): no sequence all-gather
    reaches the HLO.  ``ctx_axis`` is the SPMD-side variant of the same
    dispatch: when the caller already sits inside a manual region with a
    live ctx axis (the pipeline stage body), x is the LOCAL shard,
    ``positions`` carry global positions, and the ring runs directly.
    """
    hd = cfg.resolved_head_dim
    with jax.named_scope("attn_qkv"):
        q = _split_heads(jnp.einsum("bsd,dh->bsh", x, p["wq"]), cfg.num_heads, hd)
        k = _split_heads(jnp.einsum("bsd,dh->bsh", x, p["wk"]), cfg.num_kv_heads, hd)
        v = _split_heads(jnp.einsum("bsd,dh->bsh", x, p["wv"]), cfg.num_kv_heads, hd)
    with jax.named_scope("attn_rope"):
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    ring_gspmd = (policy is not None and mode == "train"
                  and policy.active_ctx_axis is not None and ctx_axis is None)
    if (ring_gspmd or ctx_axis is not None) and use_flash:
        raise ValueError(
            "use_flash is not supported with context parallelism: the "
            "Pallas kernel owns the whole (gathered) KV sequence; drop "
            "--use-flash or the ctx axis")
    if policy is not None:
        if mode == "decode":
            if getattr(policy, "kv_layout", "kvdim") == "kvseq":
                # flash-decoding over SEQUENCE shards: q replicated on the
                # model axis; the pv contraction psums tiny per-shard
                # output partials (the paper's sum-reduce of linear
                # partials) instead of full score vectors.
                q = policy.constrain(q, "batch", None, None, None)
            else:
                # head_dim sharded to match the cache: the score
                # contraction psums partials over the model axis.
                q = policy.constrain(q, "batch", None, None, "kvdim")
        elif ring_gspmd:
            # ring path: q/k/v stay sequence-sharded over the ctx axis;
            # the shard_map boundary below replaces the SP->TP gather.
            pass
        else:
            # heads over model axis; seq gathered (the SP->TP transition)
            q = policy.constrain(q, "batch", None, "heads", None)

    new_cache = None
    if mode in ("train", "prefill"):
        with jax.named_scope("attn_core"):
            if ctx_axis is not None and mode == "train":
                # SPMD-side ring: already inside a manual region (pipeline
                # stage body) with local sequence shards.
                from repro.core.ring_attention import ring_attention
                out = ring_attention(q, k, v, ctx_axis, chunk=cfg.attn_chunk,
                                     unroll=cfg.unroll_scans)
            elif ring_gspmd:
                from repro.core.ring_attention import ring_attention_gspmd
                out = ring_attention_gspmd(q, k, v, policy, chunk=cfg.attn_chunk,
                                           unroll=cfg.unroll_scans)
            elif use_flash:
                from repro.kernels import ops as kops
                out = kops.flash_attention(q, k, v, causal=True)
            else:
                out = blockwise_attention(q, k, v, chunk=cfg.attn_chunk,
                                          unroll=cfg.unroll_scans)
        if mode == "prefill":
            # the cache's order (B, KH, S, hd): the one decode reads
            k, v = k.swapaxes(1, 2), v.swapaxes(1, 2)
            if policy is not None:
                k = policy.constrain(k, "batch", None, None, "kvdim")
                v = policy.constrain(v, "batch", None, None, "kvdim")
            new_cache = {"k": k, "v": v}
    else:  # decode: the layer reads its cache; model.forward writes the token
        assert cache is not None
        k, v = k.swapaxes(1, 2), v.swapaxes(1, 2)              # (B, KH, 1, hd)
        k_cache, v_cache = cache["k"], cache["v"]
        if policy is not None:
            with jax.named_scope("attn_cache"):
                if getattr(policy, "kv_layout", "kvdim") == "kvseq":
                    k_cache = policy.constrain(k_cache, "batch", None, "kvseq", None)
                    v_cache = policy.constrain(v_cache, "batch", None, "kvseq", None)
                else:
                    k_cache = policy.constrain(k_cache, "batch", None, None, "kvdim")
                    v_cache = policy.constrain(v_cache, "batch", None, None, "kvdim")
        with jax.named_scope("attn_core"):
            out = decode_attention(q, k_cache, v_cache, cache_len, k, v)
        new_cache = {"k": k, "v": v}

    with jax.named_scope("attn_out"):
        out = out.reshape(out.shape[0], out.shape[1], cfg.num_heads * hd)
        out = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    return out, new_cache
