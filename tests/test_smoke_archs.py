"""Per-architecture smoke tests: REDUCED config (same family/structure,
tiny dims), one forward + one train step on CPU, asserting shapes and
finiteness.  Full configs are exercised only via the dry-run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, applicable_shapes, get_config, reduced
from repro.models import forward, init_params
from repro.optim import make_optimizer
from repro.train import build_train_step, init_train_state

B, S = 2, 64


def _batch(cfg, key):
    if cfg.frontend != "none":
        return {
            "embeds": jax.random.normal(key, (B, S, cfg.d_model), jnp.float32),
            "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        }
    tok = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    return {"tokens": tok, "labels": (tok + 1) % cfg.vocab_size}


@pytest.fixture(scope="module")
def keyring():
    return jax.random.PRNGKey(42)


@pytest.mark.parametrize("arch", ARCH_IDS)
class TestArchSmoke:
    def test_forward_shapes_finite(self, arch, keyring):
        cfg = reduced(get_config(arch))
        params = init_params(cfg, keyring)
        logits, _, aux = forward(params, _batch(cfg, keyring), cfg, None,
                                 mode="train")
        assert logits.shape == (B, S, cfg.vocab_size)
        assert bool(jnp.isfinite(logits).all()), "non-finite logits"
        assert bool(jnp.isfinite(aux)), "non-finite aux loss"
        if cfg.num_experts:
            assert float(aux) > 0.0   # router entropy produces a real aux

    def test_one_train_step(self, arch, keyring):
        cfg = reduced(get_config(arch))
        params = init_params(cfg, keyring)
        opt = make_optimizer("adamw", total_steps=10)
        state = init_train_state(cfg, params, opt)
        step = jax.jit(build_train_step(cfg, None, opt))
        new_state, metrics = step(state, _batch(cfg, keyring))
        assert int(new_state["step"]) == 1
        assert np.isfinite(float(metrics["loss"]))
        assert np.isfinite(float(metrics["grad_norm"]))
        # parameters actually moved
        moved = jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                       - b.astype(jnp.float32)).max()),
            state["params"], new_state["params"])
        assert max(jax.tree_util.tree_leaves(moved)) > 0

    def test_config_fidelity(self, arch, keyring):
        """The FULL config matches the assignment row exactly."""
        cfg = get_config(arch)
        table = {
            "glm4-9b": (40, 4096, 32, 2, 13696, 151552),
            "phi4-mini-3.8b": (32, 3072, 24, 8, 8192, 200064),
            "mistral-large-123b": (88, 12288, 96, 8, 28672, 32768),
            "phi3-medium-14b": (40, 5120, 40, 10, 17920, 100352),
            "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536),
            "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
            "pixtral-12b": (40, 5120, 32, 8, 14336, 131072),
            "kimi-k2-1t-a32b": (61, 7168, 64, 8, 2048, 163840),
            "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
            "mamba2-370m": (48, 1024, 0, 0, 0, 50280),
        }
        L, d, h, kv, ff, V = table[arch]
        assert cfg.num_layers == L and cfg.d_model == d
        assert cfg.num_heads == h and cfg.num_kv_heads == kv
        assert (cfg.moe_d_ff if arch == "kimi-k2-1t-a32b" else cfg.d_ff) == ff
        assert cfg.vocab_size == V
        # MoE structure
        moe_table = {"jamba-v0.1-52b": (16, 2), "kimi-k2-1t-a32b": (384, 8),
                     "llama4-maverick-400b-a17b": (128, 1)}
        if arch in moe_table:
            E, k = moe_table[arch]
            assert cfg.num_experts == E and cfg.experts_per_token == k
        if arch == "mamba2-370m":
            assert cfg.ssm_state == 128

    def test_shape_applicability(self, arch, keyring):
        cfg = get_config(arch)
        shapes = applicable_shapes(cfg)
        if cfg.family in ("ssm", "hybrid"):
            assert "long_500k" in shapes
        else:
            assert "long_500k" not in shapes
        assert {"train_4k", "prefill_32k", "decode_32k"} <= set(shapes)


@pytest.mark.parametrize("arch", ["glm4-9b", "jamba-v0.1-52b", "mamba2-370m"])
def test_prefill_then_decode_matches_full_forward(arch):
    """KV/SSM-cache correctness: prefill(S) + decode(1) == forward(S+1)."""
    cfg = reduced(get_config(arch))
    key = jax.random.PRNGKey(7)
    params = init_params(cfg, key)
    tok = jax.random.randint(key, (B, S + 1), 0, cfg.vocab_size)

    full_logits, _, _ = forward(params, {"tokens": tok}, cfg, None, mode="train")

    pre_logits, cache, _ = forward(params, {"tokens": tok[:, :S]}, cfg, None,
                                   mode="prefill")
    # pad caches to S+8 max length
    def pad(l):
        if l.ndim >= 4 and l.shape[3] == S:      # (n_super,B,kh,S,hd)
            pad_width = [(0, 0)] * l.ndim
            pad_width[3] = (0, 8)
            return jnp.pad(l, pad_width)
        return l
    cache = jax.tree_util.tree_map(pad, cache)
    dec_logits, _, _ = forward(params, {"tokens": tok[:, S:S + 1],
                                        "cache_len": jnp.int32(S)},
                               cfg, None, mode="decode", cache=cache)
    np.testing.assert_allclose(np.asarray(dec_logits[:, 0]),
                               np.asarray(full_logits[:, S]),
                               atol=2e-2, rtol=2e-2)
