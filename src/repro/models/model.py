"""DecoderLM: the unified decoder-only model over all assigned architectures.

One implementation covers dense (glm4/phi/mistral), MoE (kimi/llama4),
hybrid (jamba), SSM (mamba2), and stub-frontend (musicgen/pixtral) archs,
selected entirely by ModelConfig.  Parameters are stacked per superblock and
scanned (compile time O(block period)); the scan body is rematerialized
(``cfg.remat``) so only the sequence-sharded residual is saved per layer.

Modes:
  train   — full sequence, returns logits (for the loss in train/step.py)
  prefill — full sequence, also returns the KV/SSM caches
  decode  — single token against the caches (serve_step)
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from .blocks import pipeline_stage_body, superblock_apply, superblock_init
from .common import dense_init, rmsnorm


def init_params(cfg, key, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    n_super = cfg.num_layers // cfg.block_period
    k_emb, k_blocks, k_head = jax.random.split(key, 3)
    params = {
        "embed": dense_init(k_emb, cfg.vocab_size, cfg.d_model, dtype),
        "norm_final": jnp.ones((cfg.d_model,), jnp.float32),
        "blocks": jax.vmap(lambda k: superblock_init(k, cfg, dtype))(
            jax.random.split(k_blocks, n_super)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.vocab_size, dtype)
    return params


# ---------------------------------------------------------------------------
# Pipeline-parallel model cut (core/pipeline.py executor glue).
#
# The decoder is cut into S homogeneous stages along the layer axis: the
# stacked superblock parameters (n_super, ...) are re-stacked to
# (S, n_super/S, ...) with the leading dim sharded over the pipe mesh axis,
# the embedding becomes the stage-0 prologue and the final-norm + head the
# last-stage epilogue.  ``to_pipeline_params``/``from_pipeline_params`` are
# exact inverses so tests can map gradients back onto the dense layout.
#
# The same cut serves the hybrid DP x pipe x tensor mesh (DESIGN §5): no
# parameter dimension ever names the data axis, so every leaf is REPLICATED
# across replicas — the paper's parameter broadcast B — and the executor's
# end-of-drain psum over the data axis is its Eq. 9 adjoint R.
# ---------------------------------------------------------------------------

def _check_pipelineable(cfg):
    if cfg.tie_embeddings:
        raise NotImplementedError(
            "pipeline cut needs untied embeddings (the tied table would "
            "live on both the first and last stage)")
    if cfg.frontend != "none":
        raise NotImplementedError(
            "pipeline cut supports token frontends only")


def to_pipeline_params(cfg, params, num_stages: int):
    """Re-cut a dense params tree into {'pre', 'stage', 'post'} for
    ``num_stages`` pipeline stages (stage leaves stacked (S, n_super/S, ...))."""
    _check_pipelineable(cfg)
    n_super = cfg.num_layers // cfg.block_period
    if num_stages < 1 or n_super % num_stages:
        raise ValueError(
            f"{n_super} superblocks do not assign uniformly to "
            f"{num_stages} stages (the SPMD executor needs equal stages)")
    per = n_super // num_stages
    stages = jax.tree_util.tree_map(
        lambda a: a.reshape((num_stages, per) + a.shape[1:]),
        params["blocks"])
    return {
        "pre": {"embed": params["embed"]},
        "stage": stages,
        "post": {"norm_final": params["norm_final"],
                 "lm_head": params["lm_head"]},
    }


def from_pipeline_params(pparams):
    """Inverse of ``to_pipeline_params``: back to the dense layout."""
    blocks = jax.tree_util.tree_map(
        lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]),
        pparams["stage"])
    return {"embed": pparams["pre"]["embed"], "blocks": blocks,
            "norm_final": pparams["post"]["norm_final"],
            "lm_head": pparams["post"]["lm_head"]}


def init_pipeline_params(cfg, key, num_stages: int, dtype=None):
    """Initialize parameters directly in the pipeline-stage layout."""
    return to_pipeline_params(cfg, init_params(cfg, key, dtype), num_stages)


def pipeline_param_parts(cfg, policy, pparams):
    """``Partitioned`` declarations for a pipeline params tree.

    Stage leaves lead with the ``pipe`` axis (the stacked stage dim); under
    ``policy.explicit_tp`` the projection/norm leaves additionally carry
    their model-axis TP sharding (mirroring the fused TP sublayer's specs).
    MoE expert weights shard their E dim over the logical ``ep`` axis (the
    dedicated expert-parallel axis when live, replicated otherwise —
    DESIGN §8); router/shared-expert leaves stay ep-replicated (their
    dispatch runs identically on every ep rank).  pre/post leaves stay
    replicated.  No declaration names the data axis: on a hybrid mesh all
    parameters are replicated across DP replicas (the broadcast whose
    adjoint is the drain-tail gradient sum-reduce).
    """
    from repro.sharding import Partitioned

    explicit = policy is not None and getattr(policy, "explicit_tp", False)
    col = Partitioned("pipe", None, None, "model")
    row = Partitioned("pipe", None, "model", None)
    vec = Partitioned("pipe", None, "model")
    tp_table = {"wq": col, "wk": col, "wv": col, "wo": row,
                "w_up": col, "w_gate": col, "w_down": row,
                "norm_mixer": vec, "norm_ffn": vec}
    # (S, per, E, ..., ...): E — dim 2 — splits over the ep axis.
    expert_part = Partitioned("pipe", None, "ep", None, None)

    def stage_part(path, leaf):
        del leaf
        keys = [getattr(k, "key", None) for k in path]
        name = keys[-1]
        if "moe" in keys:
            # MoE sublayer (models/moe.py::moe_stage_body): expert weights
            # live in (E/ep, ...) blocks; everything else — router, shared
            # experts — replicates over ep AND model (the dispatch math is
            # duplicated on every model rank under explicit TP).
            if name in ("we_up", "we_gate", "we_down"):
                return expert_part
            return Partitioned("pipe")
        if explicit and name in tp_table:
            return tp_table[name]
        return Partitioned("pipe")

    rep = lambda tree: jax.tree_util.tree_map(lambda _: Partitioned(), tree)
    return {
        "pre": rep(pparams["pre"]),
        "stage": jax.tree_util.tree_map_with_path(stage_part,
                                                  pparams["stage"]),
        "post": rep(pparams["post"]),
    }


def pipeline_fns(cfg, policy, aux_weight: float = 0.01):
    """(pre_fn, stage_fn, logits_fn) for the pipeline executor.

    pre_fn embeds a token microbatch (and feature-shards the residual under
    explicit TP — its parameter cotangent is then in contribution form over
    the model axis, see pipeline_value_and_grad's ``pre_psum_axes``);
    stage_fn applies this stage's superblocks; logits_fn gathers the
    features back and applies final norm + head.

    MoE configs make stage_fn return ``(act, aux_weight * aux)`` — the
    stage's weighted load-balance auxiliary loss on the executor's
    ``stage_aux`` channel (same ``aux_weight`` default as
    train.build_loss_fn); dense configs return the bare activation.
    """
    from repro.core import layers as L
    from repro.core import primitives as prim

    _check_pipelineable(cfg)
    explicit = policy is not None and getattr(policy, "explicit_tp", False)
    dtype = jnp.dtype(cfg.dtype)
    has_moe = bool(cfg.num_experts)

    def pre_fn(p_pre, mb):
        x = jnp.take(p_pre["embed"], mb["tokens"], axis=0).astype(dtype)
        if explicit:
            x = L.shard_slice(x, policy.model_axis, x.ndim - 1)
        return x

    def stage_fn(p_stage, x):
        B, S_loc = x.shape[:2]
        # Under context parallelism x is the ctx rank's sequence shard:
        # positions must be GLOBAL (RoPE phases and the ring's causal
        # offsets both key on them), so offset by the rank's first row.
        pos0 = 0
        ctx = policy.active_ctx_axis if policy is not None else None
        if ctx is not None:
            pos0 = jax.lax.axis_index(ctx) * S_loc
        positions = jnp.broadcast_to(pos0 + jnp.arange(S_loc)[None, :],
                                     (B, S_loc))
        out = pipeline_stage_body(p_stage, x, cfg, policy,
                                  positions=positions)
        if has_moe:
            y, aux = out
            return y, aux_weight * aux
        return out

    def logits_fn(p_post, y):
        if explicit:
            # Replicated-adjoint gather: the epilogue loss is evaluated
            # identically on every model rank and the scheduler seeds each
            # rank's cotangent at 1, so the adjoint is the restriction to
            # the rank's own feature block (DESIGN §4 cotangent convention).
            y = prim.all_gather_replicated(y, policy.model_axis, y.ndim - 1)
        x = rmsnorm(y, p_post["norm_final"])
        return jnp.einsum("bsd,dv->bsv", x, p_post["lm_head"])

    return pre_fn, stage_fn, logits_fn


def init_cache(cfg, batch: int, max_seq: int, dtype=None):
    """Decode caches for every layer, stacked per superblock (scan layout)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    n_super = cfg.num_layers // cfg.block_period
    hd = cfg.resolved_head_dim

    def one(pos):
        kind = cfg.mixer_kind(pos)
        if kind == "attn":
            # sequence next to head_dim: the order decode's contractions read
            shape = (n_super, batch, cfg.num_kv_heads, max_seq, hd)
            return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        din = cfg.d_inner
        return {
            "conv": jnp.zeros((n_super, batch, cfg.conv_kernel - 1, din), dtype),
            "ssm": jnp.zeros((n_super, batch, cfg.ssm_heads, cfg.ssm_head_dim,
                              cfg.ssm_state), jnp.float32),
        }

    return {f"pos{i}": one(i) for i in range(cfg.block_period)}


def write_cache(cache, new, start):
    """``cache`` with ``new`` written in: each attention leaf (``k``, ``v``;
    ``(n_super, B, KH, max_seq, hd)``) takes ``new``'s positions from slot
    ``start`` on, in place where the cache is donated; the SSM leaves
    (``conv``, ``ssm``) are small states, replaced whole."""
    idx = jnp.reshape(start, ())

    def write(path, old, src):
        src = src.astype(old.dtype)
        if path[-1].key in ("k", "v"):
            return jax.lax.dynamic_update_slice_in_dim(old, src, idx, axis=3)
        return src

    return jax.tree_util.tree_map_with_path(write, cache, new)


def forward(params, batch, cfg, policy=None, *, mode="train", cache=None,
            use_flash=False):
    """Returns (logits, new_cache, aux_loss).

    batch: {"tokens": (B,S) int32} or {"embeds": (B,S,d)} for stub
    frontends; decode additionally takes {"cache_len": ()} and S == 1.
    """
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        if "embeds" in batch:
            x = batch["embeds"]
            B, S = x.shape[:2]
        else:
            tokens = batch["tokens"]
            B, S = tokens.shape
            x = jnp.take(params["embed"], tokens, axis=0)
        x = x.astype(dtype)

    cache_len = batch.get("cache_len", jnp.zeros((), jnp.int32))
    if mode == "decode":
        positions = jnp.broadcast_to(jnp.reshape(cache_len, (1, 1)), (B, 1))
    else:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    if policy is not None and mode != "decode":
        x = policy.constrain(x, "batch", "seq", None)

    def sb(carry, inp):
        x, aux = carry
        p_blk, cache_blk = inp
        x, new_cache, aux_i = superblock_apply(
            p_blk, x, cfg, policy, positions=positions, mode=mode,
            cache=cache_blk, cache_len=cache_len, use_flash=use_flash)
        return (x, aux + aux_i), new_cache

    body = sb
    if cfg.remat and mode == "train":
        body = jax.checkpoint(sb, prevent_cse=False)

    # None-valued cache dict contributes no scan leaves (train/prefill build
    # caches from scratch); a real cache is stacked (n_super, ...) per pos.
    # In decode the scan only reads it: the layers return the new token's
    # k/v (and their whole SSM states), written after the scan.
    cache_xs = cache if cache is not None else {
        f"pos{i}": None for i in range(cfg.block_period)}

    with jax.named_scope("layers"):
        (x, aux), new_cache = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), (params["blocks"], cache_xs),
            unroll=cfg.unroll_scans)
    if mode == "decode":
        with jax.named_scope("attn_cache"):
            new_cache = write_cache(cache, new_cache, cache_len)

    with jax.named_scope("final_norm"):
        x = rmsnorm(x, params["norm_final"])
    with jax.named_scope("head"):
        head = params.get("lm_head")
        if head is None:
            logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
        else:
            logits = jnp.einsum("bsd,dv->bsv", x, head)
        if policy is not None:
            # vocab owns the model axis here; the seq dim stays replicated
            # under plain SP ('seq' and 'vocab' map to the same physical axis)
            # but rides the ctx axis under context parallelism — "ctx" resolves
            # replicated when no ctx axis is live, so cp=1 is unchanged.
            logits = policy.constrain(logits, "batch", "ctx", "vocab")
    return logits, new_cache, aux
