"""Shared model components: norms, RoPE, MLPs, initialization.

Named scopes.  The forward puts each of its sublayers under one flat
``jax.named_scope`` name, so that a device trace can be charged to them
(the names live in the ops' metadata only: they change no computation).

- ``embed``: the token lookup (``model.forward``);
- ``layers``: the scan over the stacked layers; the scan's own slicing of
  the stacked weights and caches, its stacking of the new caches and its
  loop counter carry no inner name;
- in the layer body (``blocks.sublayer_apply``, ``attention.attention_block``):
  ``norm`` the two RMSNorms; ``attn_qkv`` the q/k/v projections;
  ``attn_rope`` the rotary embedding; ``attn_cache`` decode's writes of the
  new key and value into the cache; ``attn_core`` the attention contraction
  (blockwise, decode, flash or ring); ``attn_out`` the output projection
  and the residual add that joins it; ``ffn`` the dense MLP and its
  residual add;
- ``final_norm`` and ``head``: the last RMSNorm and the vocabulary
  projection (``model.forward``; the serve engine's pick of the last
  position is ``head`` too);
- ``cache_init``: the serve engine's preallocated cache and the prefill's
  write into it (``serve.engine.ServeEngine``).

MoE, SSM, the fused tensor-parallel body and the optimizer carry none yet.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import primitives as prim

SCOPES = ("embed", "layers", "norm", "attn_qkv", "attn_rope", "attn_cache",
          "attn_core", "attn_out", "ffn", "final_norm", "head", "cache_init")


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm computed in fp32 (point-wise: embarrassingly parallel)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


def rmsnorm_sharded(x: jax.Array, w: jax.Array, axis, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with the FEATURE dim sharded over ``axis`` (explicit-TP
    residual layout): the mean of squares is assembled with the paper's
    sum-reduce R; w is the matching local shard.  Call inside shard_map."""
    xf = x.astype(jnp.float32)
    d = x.shape[-1] * prim.axis_size(axis)
    ss = prim.sum_reduce(jnp.sum(xf * xf, axis=-1, keepdims=True), axis)
    out = xf * jax.lax.rsqrt(ss / d + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.  x: (B, S, H, hd); positions: (B, S) int32.

    Uses the half-split pairing (i, i+hd/2).  Computed in fp32.
    """
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def mlp_apply(x: jax.Array, p: dict, mlp_type: str) -> jax.Array:
    """Dense FFN: SwiGLU or GeLU."""
    if mlp_type == "swiglu":
        h = jnp.einsum("...d,df->...f", x, p["w_up"])
        g = jnp.einsum("...d,df->...f", x, p["w_gate"])
        h = jax.nn.silu(g) * h
    else:
        h = jnp.einsum("...d,df->...f", x, p["w_up"])
        h = jax.nn.gelu(h)
    return jnp.einsum("...f,fd->...d", h, p["w_down"])


def mlp_init(key, d: int, ff: int, mlp_type: str, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = 1.0 / np.sqrt(d)
    s_out = 1.0 / np.sqrt(ff)
    p = {
        "w_up": (jax.random.normal(k1, (d, ff), jnp.float32) * s_in).astype(dtype),
        "w_down": (jax.random.normal(k2, (ff, d), jnp.float32) * s_out).astype(dtype),
    }
    if mlp_type == "swiglu":
        p["w_gate"] = (jax.random.normal(k3, (d, ff), jnp.float32) * s_in).astype(dtype)
    return p


def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) / np.sqrt(d_in)).astype(dtype)
