"""Optimizers, schedules, gradient compression, data pipeline."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.data import DataConfig, SyntheticLM
from repro.optim import (Adafactor, AdamW, clip_by_global_norm,
                         compress_grads, global_norm, warmup_cosine)


def _quadratic_losses(opt, steps=60):
    target = jnp.array([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    state = opt.init(params)
    losses = []
    for _ in range(steps):
        grads = jax.grad(lambda p: ((p["w"] - target) ** 2).sum())(params)
        params, state = opt.update(grads, state, params)
        losses.append(float(((params["w"] - target) ** 2).sum()))
    return losses


def test_adamw_converges_on_quadratic():
    opt = AdamW(lr=lambda s: 0.1)
    losses = _quadratic_losses(opt)
    assert losses[-1] < 1e-2 * losses[0]


def test_adamw_bf16_moments_close_to_fp32():
    l32 = _quadratic_losses(AdamW(lr=lambda s: 0.1))
    l16 = _quadratic_losses(AdamW(lr=lambda s: 0.1,
                                  moment_dtype=jnp.bfloat16))
    assert l16[-1] < 1e-1 * l16[0]
    assert abs(np.log10(l16[-1] + 1e-12) - np.log10(l32[-1] + 1e-12)) < 3

def test_adafactor_converges():
    opt = Adafactor(lr=lambda s: 0.3)
    losses = _quadratic_losses(opt, steps=100)
    assert losses[-1] < 1e-1 * losses[0]


def test_adafactor_factored_state_is_small():
    opt = Adafactor(lr=lambda s: 0.1)
    params = {"w": jnp.zeros((128, 256))}
    state = opt.init(params)
    v = state["v"]["w"]
    assert v["vr"].shape == (128,) and v["vc"].shape == (256,)
    # factored second moment: 384 floats vs 32768 for full AdamW
    assert v["vr"].size + v["vc"].size < params["w"].size // 10


def test_warmup_cosine_shape():
    lr = warmup_cosine(1e-3, warmup=10, total=100)
    assert float(lr(jnp.int32(0))) < 2e-4
    assert abs(float(lr(jnp.int32(10))) - 1e-3) < 1e-4
    assert float(lr(jnp.int32(100))) < 2e-4


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert abs(float(global_norm(clipped)) - 1.0) < 1e-5
    assert abs(float(norm) - 20.0) < 1e-4


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000))
def test_stochastic_rounding_unbiased_property(seed):
    """E[sr(x)] == x: the estimator the compressed DP sum relies on."""
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(jax.random.fold_in(key, 1), (512,)) * 0.01
    samples = []
    for i in range(64):
        g = compress_grads({"x": x}, jnp.bfloat16,
                           key=jax.random.fold_in(key, i))
        samples.append(np.asarray(g["x"], np.float32))
    mean = np.stack(samples).mean(0)
    # bf16 has ~3 decimal digits; the MEAN of 64 draws must beat a single
    # round-to-nearest cast's bias floor
    err_mean = np.abs(mean - np.asarray(x)).mean()
    err_single = np.abs(np.asarray(x, np.float32)
                        - np.asarray(jnp.asarray(x, jnp.bfloat16), np.float32)).mean()
    assert err_mean < err_single


def test_data_pipeline_deterministic_and_sharded():
    cfg = DataConfig(vocab_size=100, seq_len=16, global_batch=8)
    d = SyntheticLM(cfg)
    b1, b2 = d.batch(3), d.batch(3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # host sharding partitions the global batch
    h0 = SyntheticLM(DataConfig(vocab_size=100, seq_len=16, global_batch=8,
                                host_index=0, host_count=2))
    h1 = SyntheticLM(DataConfig(vocab_size=100, seq_len=16, global_batch=8,
                                host_index=1, host_count=2))
    g = d.batch(5)["tokens"]
    np.testing.assert_array_equal(h0.batch(5)["tokens"], g[:4])
    np.testing.assert_array_equal(h1.batch(5)["tokens"], g[4:])
    # labels are next-token shifted
    b = d.batch(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])
