"""The train and serve entry points as library calls: argv in, results
out, the depth cut, the donated train state and where the compile cache
goes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced, with_layers
from repro.launch import compile_cache
from repro.launch import serve as serve_launcher
from repro.launch import train as train_launcher
from repro.models import forward, init_params
from repro.optim import make_optimizer
from repro.train import build_train_step, init_train_state


@pytest.fixture
def no_cache(monkeypatch):
    """The launchers turn the persistent cache on; tests never do."""
    for mod in (train_launcher, serve_launcher):
        monkeypatch.setattr(mod, "enable_compile_cache", lambda: None)


def test_compile_cache_placement(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/outside")
    assert compile_cache.enable_compile_cache() == "/cache/from/outside"
    assert calls == []                       # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    got = compile_cache.enable_compile_cache()
    assert calls == [("jax_compilation_cache_dir", got)]
    assert got == str(compile_cache.CHECKOUT_CACHE_DIR)
    assert got.endswith(".jax_cache")


def test_compile_log_records_each_compile():
    import time

    compile_cache.log_compiles()
    compile_cache.log_compiles()                 # one listener, not two
    x = jnp.arange(7.0)
    f = jax.jit(lambda x: x * 3 + 1)
    before = len(compile_cache.compiles())
    t0 = time.perf_counter()
    f(x).block_until_ready()
    t1 = time.perf_counter()
    new = compile_cache.compiles()[before:]
    assert len(new) == 1
    name, start, end = new[0]
    assert "lambda" in name
    assert t0 - 0.01 <= start <= end <= t1 + 0.01
    f(x).block_until_ready()                     # cached: no compile
    assert len(compile_cache.compiles()) == before + 1


def test_with_layers_cuts_depth_only():
    cfg = get_config("phi4-mini-3.8b")
    cut = with_layers(cfg, 2)
    assert cut.num_layers == 2
    assert (cut.d_model, cut.num_heads, cut.num_kv_heads, cut.d_ff,
            cut.vocab_size) == (3072, 24, 8, 8192, 200064)
    with pytest.raises(ValueError, match="block period"):
        with_layers(cfg, 0)
    moe = get_config("llama4-maverick-400b-a17b")     # block period 2
    with pytest.raises(ValueError, match="block period"):
        with_layers(moe, 3)


def test_train_step_donates_state():
    cfg = reduced(get_config("phi4-mini-3.8b"))
    opt = make_optimizer("adamw", total_steps=4)
    step = train_launcher.jit_step(build_train_step(cfg, None, opt))
    state = init_train_state(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                             opt)
    tok = jnp.zeros((2, 16), jnp.int32)
    new, _ = step(state, {"tokens": tok, "labels": tok})
    jax.block_until_ready(new)
    assert all(leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(state))
    assert int(new["step"]) == 1


def test_train_main_returns_state_and_history(no_cache):
    state, hist = train_launcher.main(
        ["--arch", "phi4-mini-3.8b", "--reduced", "--layers", "4",
         "--steps", "3", "--batch", "4", "--seq", "32"])
    assert len(hist) == 3 and int(state["step"]) == 3
    assert jax.tree_util.tree_leaves(state["params"]["blocks"])[0].shape[0] == 4
    assert all(np.isfinite(r["loss"]) and np.isfinite(r["grad_norm"])
               for r in hist)
    assert hist.health["restarts"] == 0 and hist.health["skipped_steps"] == 0


def test_serve_main_cached_logits_match_uncached_forward(no_cache):
    cfg = reduced(get_config("phi4-mini-3.8b"))
    params = init_params(cfg, jax.random.PRNGKey(3))
    out = serve_launcher.main(
        ["--arch", "phi4-mini-3.8b", "--reduced", "--batch", "2",
         "--prompt-len", "8", "--steps", "5"], params=params)
    assert out["tokens"].shape == (2, 5)
    assert out["logits"].shape == (2, 6, cfg.vocab_size)
    seq = jnp.concatenate([out["prompt"], out["tokens"]], axis=1)
    full, _, _ = forward(params, {"tokens": seq}, cfg, None, mode="train")
    np.testing.assert_allclose(np.asarray(out["logits"]),
                               np.asarray(full[:, 7:]), rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(
        np.asarray(out["tokens"]),
        np.asarray(jnp.argmax(out["logits"][:, :-1], axis=-1)))
