"""Golden regression tests: fixed-seed 2-step fp32 train losses.

Refactors of the operator algebra, the executor, or the layer stack must
not silently shift numerics: these pin the first two train-step losses of
the README quickstart configurations — the plain single-device step, the
1F1B 4-stage x 2-TP pipeline step, the hybrid (dp, S, tp) = (2, 2, 2)
step, and the context-parallel ring-attention (dp, pp, cp, tp) =
(2, 1, 2, 2) step — to values recorded at fp32 with fixed PRNG seeds
under jax 0.9.0's default threefry stream.  The stream is NOT stable
across jax versions: ``jax_threefry_partitionable`` turned on by default
in jax 0.5.0 and changed every initial weight and batch (with it set to
False, 0.9.0 reproduces the values recorded on 0.4.37 exactly), so pin
one jax.  Tolerance is tight (rtol 1e-4): loose enough for XLA
reduction-order jitter, far below any real drift.

Regenerate after an INTENTIONAL numerics change:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 REPRO_MD_SUITE=1 \
      PYTHONPATH=src python tests/md/test_golden.py
"""

import jax
import numpy as np
import pytest

from repro.configs import ModelConfig
from repro.launch.mesh import make_hybrid_mesh, make_pipeline_mesh
from repro.sharding import Policy

CFG = ModelConfig(name="golden", family="dense", num_layers=4, d_model=64,
                  num_heads=8, num_kv_heads=4, head_dim=8, d_ff=128,
                  vocab_size=256, dtype="float32", remat=False, attn_chunk=16)

# The MoE variant (PR 7): every other layer routes through 4 experts with
# top-2 gating.  capacity_factor == num_experts makes the per-expert slot
# count cover the worst-case load, so NO token is ever dropped and the
# fp32 losses are sharding-invariant: local dispatch on one device, batch
# sharded over (dp, ep), and expert-sharded (ep, tp) must all land in the
# same family.
MOE_CFG = ModelConfig(name="golden-moe", family="moe", num_layers=4,
                      d_model=64, num_heads=8, num_kv_heads=4, head_dim=8,
                      d_ff=128, vocab_size=256, dtype="float32", remat=False,
                      attn_chunk=16, num_experts=4, experts_per_token=2,
                      moe_d_ff=96, moe_layer_period=2, moe_offset=1,
                      num_shared_experts=1, capacity_factor=4.0)

# (loss after step 1, loss after step 2) — see module docstring to refresh.
# Recorded on jax 0.9.0 / CPU / 8 emulated devices.  Step-1 loss agrees
# across the first three paths (same init, same batch, fp32) to fp32
# reduction-order jitter (1.6e-7 relative) — itself a regression check on
# the single-device / pipeline / hybrid equivalence — and likewise for the
# CP ring.
GOLDEN = {
    "dense_1dev": (5.932370662689209, 5.708054065704346),
    "pipeline_1f1b_4x2": (5.932371616363525, 5.708051681518555),
    "hybrid_2x2x2": (5.932371139526367, 5.708052158355713),
    # context parallelism (PR 5): same init, same batch, sequence sharded
    # over a cp=2 ring — step-1 loss in the SAME 5.93237 family (the ring
    # merges score chunks in rotated order).
    "hybrid_cp_2x1x2x2": (5.932372093200684, 5.708051681518555),
    # expert parallelism (PR 7): same init, same batch, no-drop capacity —
    # step-1 loss IDENTICAL across local dispatch, (dp, ep) = (2, 4), and
    # (ep, tp) = (4, 2), pinning the AllToAll dispatch/combine pair and the
    # global aux-statistic reduction to the single-device reference.
    "moe_local_1dev": (6.1088151931762695, 5.854060173034668),
    "moe_dp_ep_2x4": (6.1088151931762695, 5.854060173034668),
    "moe_ep_tp_4x2": (6.1088151931762695, 5.854061126708984),
}
RTOL = 1e-4


def _batch(key):
    return {"tokens": jax.random.randint(key, (16, 16), 0, CFG.vocab_size),
            "labels": jax.random.randint(jax.random.fold_in(key, 1), (16, 16),
                                         0, CFG.vocab_size)}


def _two_losses(step, state, batch):
    out = []
    for _ in range(2):
        state, metrics = step(state, batch)
        out.append(float(jax.device_get(metrics["loss"])))
    return tuple(out)


def run_dense_1dev():
    from repro.optim import make_optimizer
    from repro.models import init_params
    from repro.train import build_train_step, init_train_state

    opt = make_optimizer("adamw", total_steps=10)
    step = jax.jit(build_train_step(CFG, None, opt))
    params = init_params(CFG, jax.random.PRNGKey(0))
    state = init_train_state(CFG, params, opt)
    return _two_losses(step, state, _batch(jax.random.PRNGKey(1)))


def _run_scheduled(mesh, builder_kw, cfg=CFG):
    from repro.optim import make_optimizer
    from repro.models import init_pipeline_params
    from repro.train import build_hybrid_train_step, init_train_state

    pol = Policy.for_mesh(mesh, explicit_tp=True)
    opt = make_optimizer("adamw", total_steps=10)
    step = jax.jit(build_hybrid_train_step(cfg, pol, opt, **builder_kw))
    params = init_pipeline_params(cfg, jax.random.PRNGKey(0), pol.pipe_size)
    state = init_train_state(cfg, params, opt)
    return _two_losses(step, state, _batch(jax.random.PRNGKey(1)))


def run_pipeline_1f1b_4x2():
    return _run_scheduled(make_pipeline_mesh(4, 2),
                          dict(num_microbatches=4, schedule="1f1b"))


def run_hybrid_2x2x2():
    return _run_scheduled(make_hybrid_mesh(2, 2, tp=2),
                          dict(num_microbatches=4, schedule="1f1b"))


def run_hybrid_cp_2x1x2x2():
    """The 4-D context-parallel step: (dp, pp, cp, tp) = (2, 1, 2, 2) —
    ring attention over the ctx axis (DESIGN §6)."""
    return _run_scheduled(make_hybrid_mesh(2, 1, 2, 2),
                          dict(num_microbatches=4, schedule="1f1b"))


def run_moe_local_1dev():
    """MoE local-dispatch reference: a (1, 1, 1) mesh — every axis is
    inactive, so dispatch/combine never leave the worker."""
    return _run_scheduled(make_hybrid_mesh(1, 1),
                          dict(num_microbatches=2, schedule="1f1b"),
                          cfg=MOE_CFG)


def run_moe_dp_ep_2x4():
    """(dp, ep) = (2, 4): tokens batch-sharded over BOTH axes, experts
    sharded over ep — dispatch is the AllToAll adjoint pair (DESIGN §8)."""
    return _run_scheduled(make_hybrid_mesh(2, 1, ep=4),
                          dict(num_microbatches=2, schedule="1f1b"),
                          cfg=MOE_CFG)


def run_moe_ep_tp_4x2():
    """(ep, tp) = (4, 2): expert parallelism composed with explicit tensor
    parallelism inside each expert's dense sublayers."""
    return _run_scheduled(make_hybrid_mesh(1, 1, tp=2, ep=4),
                          dict(num_microbatches=2, schedule="1f1b"),
                          cfg=MOE_CFG)


RUNNERS = {"dense_1dev": run_dense_1dev,
           "pipeline_1f1b_4x2": run_pipeline_1f1b_4x2,
           "hybrid_2x2x2": run_hybrid_2x2x2,
           "hybrid_cp_2x1x2x2": run_hybrid_cp_2x1x2x2,
           "moe_local_1dev": run_moe_local_1dev,
           "moe_dp_ep_2x4": run_moe_dp_ep_2x4,
           "moe_ep_tp_4x2": run_moe_ep_tp_4x2}


def _need(name):
    if name != "dense_1dev" and len(jax.devices()) < 8:
        pytest.skip("needs 8 host devices")


@pytest.mark.parametrize("name", sorted(RUNNERS))
def test_golden_two_step_losses(name):
    _need(name)
    got = RUNNERS[name]()
    want = GOLDEN[name]
    np.testing.assert_allclose(got, want, rtol=RTOL,
                               err_msg=f"{name}: regenerate goldens only "
                                       f"for INTENTIONAL numerics changes")
    assert got[1] < got[0]  # same batch twice: the step must actually learn


if __name__ == "__main__":  # golden regeneration driver
    for name, fn in sorted(RUNNERS.items()):
        print(f'    "{name}": {fn()},')
