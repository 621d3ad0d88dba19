"""Decoder blocks: (attention | SSD mixer) + (dense MLP | MoE) sub-layers.

A *superblock* is one period of the architecture's layer pattern (period 1
for uniform stacks, 8 for Jamba's [7x mamba + 1x attn] interleave, 2 for
alternating-MoE archs); model.py scans over stacked superblocks so compile
time is O(period), not O(num_layers).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import layers as L
from repro.core import primitives as prim
from repro.core.compile import dist_jit
from repro.sharding import Partitioned

from .attention import attention_block, attention_block_tp, attn_init
from .common import mlp_apply, mlp_init, rmsnorm, rmsnorm_sharded
from .moe import moe_apply, moe_init, moe_stage_body
from .ssm import ssm_block, ssm_init


def layer_kinds(cfg, layer: int) -> tuple[str, str]:
    return cfg.mixer_kind(layer), cfg.ffn_kind(layer)


def sublayer_init(key, cfg, layer: int, dtype) -> dict:
    mixer, ffn = layer_kinds(cfg, layer)
    k1, k2 = jax.random.split(key)
    p = {"norm_mixer": jnp.ones((cfg.d_model,), jnp.float32)}
    if mixer == "attn":
        p["attn"] = attn_init(k1, cfg, dtype)
    else:
        p["ssm"] = ssm_init(k1, cfg, dtype)
    if ffn != "none":
        p["norm_ffn"] = jnp.ones((cfg.d_model,), jnp.float32)
    if ffn == "mlp":
        p["mlp"] = mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype)
    elif ffn == "moe":
        p["moe"] = moe_init(k2, cfg, dtype)
    return p


def _tp_fusable(cfg, policy, mixer, ffn, mode, use_flash) -> bool:
    """The explicit-TP fused path covers the attention+MLP sublayer in
    training; everything else (SSM, MoE, prefill/decode caching, the Pallas
    flash kernel) keeps the GSPMD path."""
    if policy is None or not getattr(policy, "explicit_tp", False):
        return False
    if use_flash:
        # the fused body uses blockwise XLA attention; don't silently drop a
        # requested flash kernel
        return False
    if mode != "train" or mixer != "attn" or ffn not in ("mlp", "none"):
        return False
    tp = policy.model_size
    return (cfg.d_model % tp == 0 and cfg.num_heads % tp == 0
            and cfg.num_kv_heads % tp == 0 and cfg.d_ff % tp == 0)


def _tp_sublayer_body(p, x, positions, cfg, policy, ffn):
    """Whole sublayer on local shards: ONE shard_map spans both the
    attention and FFN halves, so their four ring collective-matmuls
    (qkv-gather, out-scatter, up-gather, down-scatter) can overlap compute
    across sub-layer boundaries.  x: (B_loc, S, d_model/tp)."""
    ax = policy.model_axis
    h = rmsnorm_sharded(x, p["norm_mixer"], ax)
    x = x + attention_block_tp(p["attn"], h, cfg, policy, positions=positions)
    if ffn == "mlp":
        h = rmsnorm_sharded(x, p["norm_ffn"], ax)
        mp = p["mlp"]
        up = L.affine_gather(h, mp["w_up"], axis=ax)
        if cfg.mlp_type == "swiglu":
            up = jax.nn.silu(L.affine_gather(h, mp["w_gate"], axis=ax)) * up
        else:
            up = jax.nn.gelu(up)
        x = x + L.affine_scatter(up, mp["w_down"], axis=ax)
    return x


def _tp_sublayer_apply(p, x, cfg, policy, *, positions, ffn):
    """dist_jit wrapper of the fused sublayer: logical Partitioned specs at
    the boundary (residual features over the model axis — the repartition
    from/to the sequence-sharded stream is inserted by GSPMD outside).
    With a live ctx axis the sequence dim ALSO stays sharded at the
    boundary ("ctx" resolves replicated otherwise), so the region composes
    ring attention on ``ctx`` with the ring collective-matmuls on
    ``model`` and no sequence gather reaches the HLO."""
    if policy.active_ctx_axis and x.shape[1] % policy.ctx_size:
        raise ValueError(
            f"sequence length {x.shape[1]} not divisible by ctx axis size "
            f"{policy.ctx_size} — a clamped shard would silently drop the "
            f"trailing positions")
    m = Partitioned("model")
    col = Partitioned(None, "model")   # (in, out-shard) projections
    row = Partitioned("model", None)   # (in-shard, out) projections
    p_parts = {"norm_mixer": m,
               "attn": {"wq": col, "wk": col, "wv": col, "wo": row}}
    p_in = {"norm_mixer": p["norm_mixer"], "attn": p["attn"]}
    if ffn == "mlp":
        p_parts["norm_ffn"] = m
        p_parts["mlp"] = {k: (row if k == "w_down" else col) for k in p["mlp"]}
        p_in["norm_ffn"] = p["norm_ffn"]
        p_in["mlp"] = p["mlp"]
    xp = Partitioned("batch", "ctx", "model")

    def body(pp, xx, pos):
        return _tp_sublayer_body(pp, xx, pos, cfg, policy, ffn)

    return dist_jit(body, policy,
                    (p_parts, xp, Partitioned("batch", "ctx")), xp,
                    jit=False)(p_in, x, positions)


def sublayer_apply(p, x, cfg, policy, layer: int, *, positions, mode,
                   cache=None, cache_len=None, use_flash=False,
                   ctx_axis=None):
    """One decoder layer: x + mixer(norm(x)); x + ffn(norm(x)).

    Returns (x, new_cache, aux_loss).  ``ctx_axis``: live ctx mesh axis
    when called on LOCAL shards inside a manual region (the pipeline stage
    body under context parallelism) — attention then rings over it instead
    of attending locally; ``positions`` must carry global positions."""
    mixer, ffn = layer_kinds(cfg, layer)
    aux = jnp.zeros((), jnp.float32)

    if _tp_fusable(cfg, policy, mixer, ffn, mode, use_flash):
        x = _tp_sublayer_apply(p, x, cfg, policy, positions=positions,
                               ffn=ffn)
        x = policy.constrain(x, "batch", "seq", None)
        return x, None, aux

    # Each residual add sits in the scope of the product it joins: XLA
    # fuses the two, and a fusion is charged to its root's scope.
    with jax.named_scope("norm"):
        h = rmsnorm(x, p["norm_mixer"])
    if mixer == "attn":
        out, new_cache = attention_block(
            p["attn"], h, cfg, policy, positions=positions, mode=mode,
            cache=cache, cache_len=cache_len, use_flash=use_flash,
            ctx_axis=ctx_axis)
        with jax.named_scope("attn_out"):
            x = x + out
    else:
        out, new_cache = ssm_block(p["ssm"], h, cfg, policy, mode=mode,
                                   cache=cache)
        x = x + out
    if policy is not None and mode != "decode":
        x = policy.constrain(x, "batch", "seq", None)

    if ffn != "none":
        with jax.named_scope("norm"):
            h = rmsnorm(x, p["norm_ffn"])
        if ffn == "mlp":
            with jax.named_scope("ffn"):
                out = mlp_apply(h, p["mlp"], cfg.mlp_type)
                if policy is not None and mode != "decode":
                    out = policy.constrain(out, "batch", "seq", None)
                x = x + out
        else:
            out, aux = moe_apply(h, p["moe"], cfg, policy)
            x = x + out
        if policy is not None and mode != "decode":
            x = policy.constrain(x, "batch", "seq", None)
    return x, new_cache, aux


def pipeline_stage_body(p_stage, x, cfg, policy, *, positions):
    """One pipeline STAGE on local shards: its stack of superblocks, applied
    inside the pipeline's shard_map region (core/pipeline.py).

    p_stage: this stage's superblocks, stacked ``(n_super_per_stage, ...)``.
    x: the local activation shard — ``(B_mb, S_loc, d_model/tp)``
    feature-sharded when ``policy.explicit_tp`` (the fused ring-TP sublayer
    bodies run inside the region, so TP collectives compose with the pipe
    axis), else the full-feature ``(B_mb, S_loc, d_model)`` residual with
    plain local math.  Under context parallelism ``S_loc`` is the ctx
    rank's sequence shard, ``positions`` carry global positions, and
    attention rings over the ctx axis in BOTH branches (the ctx, pipe and
    model axes all live in the one region).

    MoE sublayers run through :func:`repro.models.moe.moe_stage_body`
    (dispatch/combine as AllToAll adjoints on the live ep axis, DESIGN §8)
    and the stage RETURNS ``(x, aux)`` — the summed load-balance auxiliary
    loss rides the executor's ``stage_aux`` channel (core/pipeline.py)
    instead of being dropped.  Dense configs keep the plain single-carry
    scan, byte-identical to the pre-MoE path.  Under explicit_tp the MoE
    half gathers the feature-sharded residual to the full width
    (``all_gather_replicated``), runs the identical dispatch on every
    model rank, and restricts the result back to the rank's own block
    (``shard_slice_replicated`` — the replicated-cotangent adjoint pair).

    Training math only (no caches / flash kernel); each sublayer must be
    TP-fusable under explicit_tp (attention mixer, dense/absent/moe FFN).
    """
    period = cfg.block_period
    explicit = policy is not None and getattr(policy, "explicit_tp", False)
    ctx_axis = policy.active_ctx_axis if policy is not None else None
    ep_axis = policy.active_ep_axis if policy is not None else None
    # Axes the stage's TOKENS shard over — the MoE aux statistics reduce
    # over exactly these so aux is the global-microbatch value everywhere.
    stat_axes = tuple(a for a in (
        policy.active_data_axis if policy is not None else None,
        ctx_axis, ep_axis) if a)
    has_moe = any(layer_kinds(cfg, i)[1] == "moe" for i in range(period))

    def apply_block(xx, p_blk):
        aux = jnp.zeros((), jnp.float32)
        for i in range(period):
            mixer, ffn = layer_kinds(cfg, i)
            pp = p_blk[f"pos{i}"]
            if ffn == "moe":
                if explicit:
                    if mixer != "attn":
                        raise NotImplementedError(
                            "explicit-TP pipeline stages support attention "
                            f"mixers with MoE FFNs, got ({mixer}, {ffn})")
                    ax = policy.model_axis
                    xx = _tp_sublayer_body(pp, xx, positions, cfg, policy,
                                           "none")
                    h = rmsnorm_sharded(xx, pp["norm_ffn"], ax)
                    h = prim.all_gather_replicated(h, ax, 2)
                    y, aux_i = moe_stage_body(h, pp["moe"], cfg,
                                              ep_axis=ep_axis,
                                              stat_axes=stat_axes)
                    xx = xx + prim.shard_slice_replicated(y, ax, 2)
                else:
                    h = rmsnorm(xx, pp["norm_mixer"])
                    if mixer == "attn":
                        out, _ = attention_block(
                            pp["attn"], h, cfg, None, positions=positions,
                            mode="train", ctx_axis=ctx_axis)
                    else:
                        out, _ = ssm_block(pp["ssm"], h, cfg, None,
                                           mode="train")
                    xx = xx + out
                    h = rmsnorm(xx, pp["norm_ffn"])
                    y, aux_i = moe_stage_body(h, pp["moe"], cfg,
                                              ep_axis=ep_axis,
                                              stat_axes=stat_axes)
                    xx = xx + y
                aux = aux + aux_i
            elif explicit:
                if mixer != "attn" or ffn not in ("mlp", "none"):
                    raise NotImplementedError(
                        "explicit-TP pipeline stages support attention + "
                        f"dense-FFN sublayers, got ({mixer}, {ffn})")
                xx = _tp_sublayer_body(pp, xx, positions, cfg, policy, ffn)
            else:
                xx, _, _ = sublayer_apply(pp, xx, cfg, None, i,
                                          positions=positions, mode="train",
                                          ctx_axis=ctx_axis)
        return xx, aux

    if has_moe:
        def one_superblock_aux(carry, p_blk):
            xx, aux = carry
            xx, aux_i = apply_block(xx, p_blk)
            return (xx, aux + aux_i), None

        (x, aux), _ = jax.lax.scan(
            one_superblock_aux, (x, jnp.zeros((), jnp.float32)), p_stage)
        return x, aux

    def one_superblock(xx, p_blk):
        xx, _ = apply_block(xx, p_blk)
        return xx, None

    x, _ = jax.lax.scan(one_superblock, x, p_stage)
    return x


def superblock_init(key, cfg, dtype) -> dict:
    period = cfg.block_period
    keys = jax.random.split(key, period)
    return {f"pos{i}": sublayer_init(keys[i], cfg, i, dtype)
            for i in range(period)}


def superblock_apply(p, x, cfg, policy, *, positions, mode, cache=None,
                     cache_len=None, use_flash=False):
    """Apply one superblock (period consecutive layers).

    cache: dict pos->layer cache (or None).  Returns (x, caches, aux_sum).

    Layer-kind dispatch uses position within the superblock: the absolute
    layer index is s*period + pos and every kind predicate in ModelConfig
    has period dividing block_period, so kinds depend only on pos.
    """
    period = cfg.block_period
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = {}
    for i in range(period):
        sub_cache = cache.get(f"pos{i}") if cache is not None else None
        x, c, aux = sublayer_apply(
            p[f"pos{i}"], x, cfg, policy, i, positions=positions, mode=mode,
            cache=sub_cache, cache_len=cache_len, use_flash=use_flash)
        aux_total = aux_total + aux
        if c is not None:
            new_caches[f"pos{i}"] = c
    return x, new_caches, aux_total
