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


@pytest.mark.parametrize("arch", ["glm4-9b", "jamba-v0.1-52b"])
def test_decode_writes_each_token_in_its_slot(arch):
    """Prefill S tokens, then N decode steps: the engine's cache holds the
    k/v an uncached prefill over the S + N tokens gives, in slots
    [0, S + N), zeros beyond, and the SSM states of that prefill.  The
    experts' capacity holds every token, so that no token is dropped in
    the longer prefill that is not dropped in the shorter one."""
    import dataclasses

    from repro.models import init_cache

    cfg = reduced(get_config(arch))
    cfg = dataclasses.replace(cfg, capacity_factor=float(max(cfg.num_experts, 1)))
    params = init_params(cfg, jax.random.PRNGKey(8))
    S, N, max_seq = 16, 5, 40
    engine = ServeEngine(cfg, params, None, max_seq=max_seq, batch_size=2)
    seq = jax.random.randint(jax.random.PRNGKey(9), (2, S + N), 0,
                             cfg.vocab_size)
    _, cache = engine.prefill(seq[:, :S])
    for t in range(N):
        _, cache = engine.decode_step(cache, seq[:, S + t:S + t + 1],
                                      jnp.int32(S + t))
    _, want, _ = forward(params, {"tokens": seq}, cfg, None, mode="prefill")

    shapes = jax.tree_util.tree_map(jnp.shape, init_cache(cfg, 2, max_seq))
    assert jax.tree_util.tree_map(jnp.shape, cache) == shapes
    kinds = set()
    for pos, leaves in cache.items():
        for name, got in leaves.items():
            got, ref = np.asarray(got), np.asarray(want[pos][name])
            kinds.add(name)
            if name in ("k", "v"):
                np.testing.assert_allclose(got[:, :, :, :S + N], ref,
                                           rtol=1e-2, atol=1e-2)
                assert not got[:, :, :, S + N:].any()
            else:
                np.testing.assert_allclose(got, ref, rtol=1e-2, atol=1e-2)
    assert kinds == ({"k", "v", "conv", "ssm"} if cfg.family == "hybrid"
                     else {"k", "v"})


def test_decode_scan_carries_only_new_tokens(setup):
    """The layer scan of a decode step reads the stacked cache and hands
    back only the new token's k/v: no output of the scan has the cache's
    ``max_seq`` slots.  The cache is stored in the order the decode
    contractions read, (n_super, B, KH, max_seq, hd)."""
    from repro.models import init_cache

    cfg, params, _ = setup
    max_seq, B = 40, 2
    engine = ServeEngine(cfg, params, None, max_seq=max_seq, batch_size=B)
    cache = init_cache(cfg, B, max_seq)
    n_super = cfg.num_layers // cfg.block_period
    kv_heads, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    for leaf in jax.tree_util.tree_leaves(cache):
        assert leaf.shape == (n_super, B, kv_heads, max_seq, hd)

    jaxpr = jax.make_jaxpr(engine._decode_impl)(
        params, cache, jnp.zeros((B, 1), jnp.int32), jnp.int32(3))
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    ins = [v.aval.shape for v in scans[0].invars]
    outs = [v.aval.shape for v in scans[0].outvars]
    assert ins.count((n_super, B, kv_heads, max_seq, hd)) == 2
    assert not any(max_seq in shape for shape in outs)
    assert outs.count((n_super, B, kv_heads, 1, hd)) == 2
