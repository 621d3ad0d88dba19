"""Serving engine: prefill+decode vs full forward, greedy determinism,
batched generation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import forward, init_params
from repro.serve import ServeEngine


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("glm4-9b"))
    params = init_params(cfg, jax.random.PRNGKey(1))
    engine = ServeEngine(cfg, params, None, max_seq=64, batch_size=2)
    return cfg, params, engine


def test_generate_matches_teacher_forcing(setup):
    """Greedy generation must agree with argmax over a full forward pass on
    the generated prefix (cache correctness end-to-end)."""
    cfg, params, engine = setup
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                                cfg.vocab_size)
    out = engine.generate(prompt, steps=8, greedy=True)
    assert out.shape == (2, 8)

    seq = jnp.concatenate([prompt, out], axis=1)
    logits, _, _ = forward(params, {"tokens": seq}, cfg, None, mode="train")
    for t in range(8):
        expect = jnp.argmax(logits[:, 16 + t - 1], axis=-1)
        np.testing.assert_array_equal(np.asarray(out[:, t]),
                                      np.asarray(expect))


def test_generate_deterministic(setup):
    cfg, params, engine = setup
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0,
                                cfg.vocab_size)
    a = engine.generate(prompt, steps=6, greedy=True)
    b = engine.generate(prompt, steps=6, greedy=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sampling_path(setup):
    cfg, params, engine = setup
    prompt = jax.random.randint(jax.random.PRNGKey(4), (2, 8), 0,
                                cfg.vocab_size)
    out = engine.generate(prompt, steps=4, greedy=False,
                          key=jax.random.PRNGKey(0), temperature=0.8)
    assert out.shape == (2, 4)
    assert int(out.min()) >= 0 and int(out.max()) < cfg.vocab_size


def test_ssm_generation():
    """Mamba2 decode via the O(1) state recurrence agrees with
    teacher-forced argmax (state-passing correctness)."""
    cfg = reduced(get_config("mamba2-370m"))
    params = init_params(cfg, jax.random.PRNGKey(5))
    engine = ServeEngine(cfg, params, None, max_seq=48, batch_size=2)
    prompt = jax.random.randint(jax.random.PRNGKey(6), (2, 12), 0,
                                cfg.vocab_size)
    out = engine.generate(prompt, steps=6, greedy=True)
    seq = jnp.concatenate([prompt, out], axis=1)
    logits, _, _ = forward(params, {"tokens": seq}, cfg, None, mode="train")
    for t in range(6):
        expect = jnp.argmax(logits[:, 12 + t - 1], axis=-1)
        np.testing.assert_array_equal(np.asarray(out[:, t]),
                                      np.asarray(expect))


def test_programs_carry_the_scope_vocabulary(setup):
    """Every named scope of the forward's vocabulary reaches the compiled
    serve programs' op metadata: the cache write in decode only, the
    cache set-up in prefill only."""
    import re

    from repro.models.common import SCOPES

    _, _, engine = setup
    found = {}
    for name, lowered in engine.lower(16).items():
        paths = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
        found[name] = {p for path in paths for p in path.split("/")} & set(SCOPES)
    assert found["prefill"] | found["decode"] == set(SCOPES)
    assert "attn_cache" in found["decode"] and "attn_cache" not in found["prefill"]
    assert "cache_init" in found["prefill"] and "cache_init" not in found["decode"]


def test_lowered_programs_are_the_ones_that_run(setup):
    """``ServeEngine.lower`` from shapes gives the same compiled programs
    as the engine's own calls on arrays."""
    cfg, _, engine = setup
    prompt = jnp.zeros((2, 16), jnp.int32)
    lowered = engine.lower(16)
    _, cache = engine.prefill(prompt)
    ran = {"prefill": engine._prefill.lower(engine.params, {"tokens": prompt}),
           "decode": engine._decode.lower(engine.params, cache,
                                          jnp.zeros((2, 1), jnp.int32),
                                          jnp.int32(16))}
    for name in ran:
        assert (lowered[name].compile().as_text()
                == ran[name].compile().as_text()), name
