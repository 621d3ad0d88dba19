"""MoE with real expert parallelism (paper's generalized all-to-all) vs the
single-device reference path, on an 8-device mesh."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro import compat
from repro.models.moe import moe_apply, moe_init
from repro.sharding import Policy

# ~140 s on 8 emulated devices: the CI slow leg runs it, not tier-1
# (test_golden.py keeps the (dp, ep) and (ep, tp) dispatch there)
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def setup():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 host devices")
    cfg = reduced(get_config("jamba-v0.1-52b"))
    cfg = dataclasses.replace(cfg, capacity_factor=4.0)  # avoid drops: exact
    key = jax.random.PRNGKey(0)
    p = moe_init(key, cfg, jnp.float32)
    mesh = compat.make_mesh((2, 4), ("data", "model"))
    policy = Policy(mesh=mesh)
    return cfg, p, policy


def test_ep_matches_reference(setup):
    cfg, p, policy = setup
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
    y_ep, aux_ep = moe_apply(x, p, cfg, policy)       # shard_map EP path
    y_ref, aux_ref = moe_apply(x, p, cfg, None)       # dense reference
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                               atol=2e-4, rtol=2e-4)


def test_ep_gradients_match_reference(setup):
    cfg, p, policy = setup
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 16, cfg.d_model))

    def loss(p, pol):
        y, aux = moe_apply(x, p, cfg, pol)
        return (y ** 2).sum() + 0.01 * aux

    g_ep = jax.grad(loss)(p, policy)
    g_ref = jax.grad(loss)(p, None)
    for (ka, a), (kb, b) in zip(
            sorted(jax.tree_util.tree_leaves_with_path(g_ep),
                   key=lambda t: str(t[0])),
            sorted(jax.tree_util.tree_leaves_with_path(g_ref),
                   key=lambda t: str(t[0]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4, err_msg=str(ka))


def test_capacity_drops_are_deterministic(setup):
    cfg, p, policy = setup
    tight = dataclasses.replace(cfg, capacity_factor=0.5)  # force drops
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 16, cfg.d_model))
    y1, _ = moe_apply(x, p, tight, policy)
    y2, _ = moe_apply(x, p, tight, policy)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    # dropped tokens pass through with zero expert contribution, not NaN
    assert bool(jnp.isfinite(y1).all())


def test_ep_drop_set_matches_per_block_local_dispatch(setup):
    """Under a live ep axis the batch is sub-sharded over ep, so each rank
    runs its own capacity dispatch on its token block.  Pin that the SET of
    dropped tokens (rows combining to exactly zero — the CapacityRestrict
    tail, k=1 so gates are exactly 1) per block equals an unsharded
    local-dispatch run of that block: distribution over ep must never
    change WHICH tokens drop."""
    cfg, p, _ = setup
    tight = dataclasses.replace(cfg, capacity_factor=0.5,
                                experts_per_token=1, num_shared_experts=0)
    mesh = compat.make_mesh((4,), ("ep",))
    pol = Policy.for_mesh(mesh)
    assert pol.active_ep_axis == "ep"
    x = jax.random.normal(jax.random.PRNGKey(5), (8, 16, cfg.d_model))
    y_ep, _ = moe_apply(x, p, tight, pol)
    total_drops = 0
    for i in range(4):
        blk = x[2 * i:2 * (i + 1)]
        y_ref, _ = moe_apply(blk, p, tight, None)
        got = np.asarray(y_ep[2 * i:2 * (i + 1)])
        np.testing.assert_allclose(got, np.asarray(y_ref),
                                   atol=2e-4, rtol=2e-4)
        drop_got = np.all(got == 0.0, axis=-1)
        drop_ref = np.all(np.asarray(y_ref) == 0.0, axis=-1)
        np.testing.assert_array_equal(drop_got, drop_ref)
        total_drops += int(drop_got.sum())
    assert total_drops > 0  # capacity_factor=0.5 must actually drop tokens


def _hybrid_loss_and_grads(mesh, cfg, batch, num_microbatches=2):
    from repro.models import init_pipeline_params
    from repro.train import build_hybrid_value_and_grad

    pol = Policy.for_mesh(mesh, explicit_tp=True)
    pvg, _ = build_hybrid_value_and_grad(cfg, pol,
                                         num_microbatches=num_microbatches)
    params = init_pipeline_params(cfg, jax.random.PRNGKey(0), pol.pipe_size)
    mbs = jax.tree_util.tree_map(
        lambda a: a.reshape((num_microbatches,
                             a.shape[0] // num_microbatches) + a.shape[1:]),
        batch)
    loss, grads = jax.jit(pvg)(params, {"tokens": mbs["tokens"]},
                               mbs["labels"])
    return float(jax.device_get(loss)), grads


def test_hybrid_ep_meshes_match_reference_loss_and_grads():
    """The PR-7 acceptance bar: the (dp, ep) = (2, 4) and (ep, tp) = (4, 2)
    hybrid executors must match the local-dispatch single-device reference
    in loss AND every parameter gradient (capacity covers the worst-case
    load, so no token drops and fp32 results are sharding-invariant)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 host devices")
    from repro.configs import ModelConfig
    from repro.launch.mesh import make_hybrid_mesh

    cfg = ModelConfig(name="ep-grads", family="moe", num_layers=2,
                      d_model=64, num_heads=8, num_kv_heads=4, head_dim=8,
                      d_ff=128, vocab_size=256, dtype="float32", remat=False,
                      attn_chunk=16, num_experts=4, experts_per_token=2,
                      moe_d_ff=96, moe_layer_period=2, moe_offset=1,
                      num_shared_experts=1, capacity_factor=4.0)
    key = jax.random.PRNGKey(7)
    batch = {"tokens": jax.random.randint(key, (16, 16), 0, cfg.vocab_size),
             "labels": jax.random.randint(jax.random.fold_in(key, 1),
                                          (16, 16), 0, cfg.vocab_size)}
    ref_loss, ref_g = _hybrid_loss_and_grads(make_hybrid_mesh(1, 1), cfg,
                                             batch)
    for mk, mesh in [("dp_ep", make_hybrid_mesh(2, 1, ep=4)),
                     ("ep_tp", make_hybrid_mesh(1, 1, tp=2, ep=4))]:
        loss, g = _hybrid_loss_and_grads(mesh, cfg, batch)
        np.testing.assert_allclose(loss, ref_loss, rtol=1e-5,
                                   err_msg=f"{mk}: loss")
        for (ka, a), (kb, b) in zip(
                sorted(jax.tree_util.tree_leaves_with_path(g),
                       key=lambda t: str(t[0])),
                sorted(jax.tree_util.tree_leaves_with_path(ref_g),
                       key=lambda t: str(t[0]))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=2e-4,
                                       err_msg=f"{mk}: {ka}")


def test_big_E_ep8_matches_reference():
    """Big-E leg (CI slow marks): 8 experts fully sharded over ep=8 — one
    expert block per rank — must still match the unsharded dense reference
    at drop-free capacity."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 host devices")
    cfg = reduced(get_config("jamba-v0.1-52b"))
    cfg = dataclasses.replace(cfg, num_experts=8, capacity_factor=8.0)
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    mesh = compat.make_mesh((8,), ("ep",))
    pol = Policy.for_mesh(mesh)
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 16, cfg.d_model))
    y_ep, _ = moe_apply(x, p, cfg, pol)
    y_ref, _ = moe_apply(x, p, cfg, None)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                               atol=2e-4, rtol=2e-4)


def test_num_experts_not_divisible_by_ep_raises(setup):
    """The trace-time guard (models/moe.py::_check_expert_split): a clamped
    E/ep split would silently drop the trailing experts."""
    cfg, p, _ = setup
    bad = dataclasses.replace(cfg, num_experts=cfg.num_experts + 1)
    mesh = compat.make_mesh((4,), ("ep",))
    pol = Policy.for_mesh(mesh)
    x = jax.random.normal(jax.random.PRNGKey(6), (8, 16, cfg.d_model))
    with pytest.raises(ValueError, match="not divisible by ep"):
        moe_apply(x, p, bad, pol)
