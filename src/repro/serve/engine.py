"""Serving engine: batched prefill + decode against preallocated caches.

``prefill`` runs the full forward over the prompt and writes the layer
caches into preallocated max-length buffers; ``decode_step`` appends one
token for the whole batch (the lowered ``serve_step`` of the decode_* shape
cells).  The KV cache head_dim is sharded over the model axis and the batch
over data (sharding/policy.py), so decode's score contraction runs as
psum-combined partials — the paper's sum-reduce of linear partials.

The batch advances in lockstep (one shared cache_len); continuous batching
(per-row lengths + slot recycling) is an orchestration layer above this
engine and out of scope here — noted in DESIGN.md.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.models import forward, init_cache


class ServeEngine:
    def __init__(self, cfg, params, policy=None, *, max_seq: int,
                 batch_size: int, donate_cache: bool = True):
        self.cfg = cfg
        self.params = params
        self.policy = policy
        self.max_seq = max_seq
        self.batch_size = batch_size

        self._prefill = jax.jit(partial(self._prefill_impl),
                                static_argnames=())
        self._decode = jax.jit(partial(self._decode_impl),
                               donate_argnums=(1,) if donate_cache else ())

    # -- implementation fns (pure) -------------------------------------------
    def _prefill_impl(self, params, batch):
        logits, pref_cache, _ = forward(params, batch, self.cfg, self.policy,
                                        mode="prefill")

        def write(dst, src):
            if dst.ndim >= 3 and dst.shape[2] == self.max_seq:
                return jax.lax.dynamic_update_slice_in_dim(dst, src.astype(dst.dtype),
                                                           0, axis=2)
            return src.astype(dst.dtype)   # ssm state / conv state: final

        with jax.named_scope("cache_init"):
            big = init_cache(self.cfg, self.batch_size, self.max_seq,
                             jnp.dtype(self.cfg.dtype))
            cache = jax.tree_util.tree_map(write, big, pref_cache)
        with jax.named_scope("head"):
            return logits[:, -1], cache

    def _decode_impl(self, params, cache, tokens, cache_len):
        batch = {"tokens": tokens, "cache_len": cache_len}
        logits, cache, _ = forward(params, batch, self.cfg, self.policy,
                                   mode="decode", cache=cache)
        with jax.named_scope("head"):
            return logits[:, -1], cache

    # -- public API ------------------------------------------------------------
    def lower(self, prompt_len: int) -> dict:
        """The engine's two programs lowered for prompts of ``prompt_len``
        tokens, from shapes alone: ``{"prefill": ..., "decode": ...}``
        (``jax.stages.Lowered``; ``.compile().as_text()`` is the program
        as it runs)."""
        def shape(a):     # a sharding over devices is part of the program
            s = getattr(a, "sharding", None)
            spread = s is not None and len(s.device_set) > 1
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=s if spread else None)

        params = jax.tree_util.tree_map(shape, self.params)
        batch = {"tokens": jax.ShapeDtypeStruct((self.batch_size, prompt_len),
                                                jnp.int32)}
        _, cache = jax.eval_shape(self._prefill, params, batch)
        tokens = jax.ShapeDtypeStruct((self.batch_size, 1), jnp.int32)
        cache_len = jax.ShapeDtypeStruct((), jnp.int32)
        return {"prefill": self._prefill.lower(params, batch),
                "decode": self._decode.lower(params, cache, tokens, cache_len)}

    def prefill(self, tokens):
        """tokens: (B, S_prompt) -> (last_logits, cache)."""
        return self._prefill(self.params, {"tokens": tokens})

    def decode_step(self, cache, tokens, cache_len):
        """tokens: (B, 1); cache_len: scalar int32."""
        return self._decode(self.params, cache, tokens, cache_len)

    def generate(self, prompt, steps: int, *, greedy: bool = True, key=None,
                 temperature: float = 1.0, return_logits: bool = False):
        """Greedy / temperature sampling for ``steps`` tokens.

        With ``return_logits`` also returns the ``(B, steps + 1, V)``
        logits the engine computed: entry ``t`` is the prefill's last
        position for ``t = 0`` and decode step ``t - 1`` after that — the
        cached counterparts of an uncached forward's positions
        ``S - 1 .. S - 1 + steps``.
        """
        B, S = prompt.shape
        logits, cache = self.prefill(prompt)
        out, seen = [], [logits]
        tok = self._pick(logits, greedy, key, temperature, 0)
        for t in range(steps):
            out.append(tok)
            logits, cache = self.decode_step(cache, tok, jnp.int32(S + t))
            seen.append(logits)
            tok = self._pick(logits, greedy, key, temperature, t + 1)
        tokens = jnp.concatenate(out, axis=1)
        if return_logits:
            return tokens, jnp.stack(seen, axis=1)
        return tokens

    @staticmethod
    def _pick(logits, greedy, key, temperature, t):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        k = jax.random.fold_in(key, t)
        return jax.random.categorical(k, logits / temperature, axis=-1
                                      ).astype(jnp.int32)[:, None]
