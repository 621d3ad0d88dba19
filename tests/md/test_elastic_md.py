"""Elastic recovery on the live hybrid mesh (DESIGN §10).

The headline property: training on the (dp, pp, cp, tp) = (2, 1, 2, 2)
mesh survives the permanent loss of a data-axis device slice — the
supervisor shrinks to (1, 1, 2, 2) over the four survivors, reshards the
newest verified checkpoint through the ``Repartition`` plan, folds the
lost replica into grad accumulation (``virtual_dp=2``) — and the final
fixed-seed fp32 loss AND every parameter EXACTLY match the uninterrupted
full-mesh run.  Exactness is by construction, not luck: the pipeline
epilogue reduces the data axis with its OWN psum sequenced after the
intra-replica reductions, so the degraded step's per-pass results combine
on the host along the same reduction tree (core/pipeline.py).
"""

import jax
import numpy as np
import pytest

from repro.configs import ModelConfig
from repro.launch.mesh import (make_hybrid_mesh, shrink_factorization,
                               surviving_devices)
from repro.optim import make_optimizer
from repro.models import init_pipeline_params
from repro.sharding import Policy
from repro.train import (LoopConfig, build_hybrid_train_step,
                         elastic_restart_on_failure, init_train_state, run)
from repro.resilience import DeviceLossError, FaultInjector, FaultPlan

CFG = ModelConfig(name="elastic", family="dense", num_layers=4, d_model=64,
                  num_heads=8, num_kv_heads=4, head_dim=8, d_ff=128,
                  vocab_size=256, dtype="float32", remat=False, attn_chunk=16)
TOTAL = 12
FULL = (2, 1, 2, 2, 1)                     # (dp, S, cp, tp, ep)


def _need8():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 host devices")


def _batch(i):
    key = jax.random.fold_in(jax.random.PRNGKey(1), i)
    return {"tokens": jax.random.randint(key, (16, 16), 0, CFG.vocab_size),
            "labels": jax.random.randint(jax.random.fold_in(key, 1),
                                         (16, 16), 0, CFG.vocab_size)}


def _make_iter(start):
    class It:
        def __init__(self, s):
            self.s = s

        def __next__(self):
            s = self.s
            self.s += 1
            return s, _batch(s)
    return It(start)


def _setup(fact, devices, vdp, opt):
    """The elastic supervisor's ``make_setup`` contract."""
    dp, S, cp, tp, ep = fact
    mesh = make_hybrid_mesh(dp, S, cp, tp, ep, devices=devices)
    pol = Policy.for_mesh(mesh, explicit_tp=True)
    step = jax.jit(build_hybrid_train_step(
        CFG, pol, opt, num_microbatches=4, schedule="1f1b",
        virtual_dp=vdp))

    def make_state():
        params = init_pipeline_params(CFG, jax.random.PRNGKey(0),
                                      pol.pipe_size)
        return init_train_state(CFG, params, opt)

    return mesh, make_state, step, None


def _assert_states_equal(state, golden):
    for a, b in zip(jax.tree_util.tree_leaves(state["params"]),
                    jax.tree_util.tree_leaves(golden["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_shrink_helpers_drop_last_slice():
    """surviving_devices keeps the data-axis-0 sub-grid in order;
    shrink_factorization halves the lost degree and reports the fold."""
    _need8()
    mesh = make_hybrid_mesh(*FULL[:4])
    survivors = surviving_devices(mesh, "data")
    assert [d.id for d in survivors] == [0, 1, 2, 3]
    assert shrink_factorization(FULL, "data") == ((1, 1, 2, 2, 1), 2)
    assert shrink_factorization(FULL, "ctx") == ((2, 1, 1, 2, 1), 2)
    with pytest.raises(ValueError, match="degree 1"):
        shrink_factorization(FULL, "pipe")
    with pytest.raises(ValueError, match="size 1"):
        surviving_devices(mesh, "pipe")
    # the degraded factorization over the survivors is legal; the lost
    # one oversubscribes — the exact probe the supervisor runs
    make_hybrid_mesh(1, 1, 2, 2, devices=survivors)
    with pytest.raises(ValueError, match="oversubscribes"):
        make_hybrid_mesh(2, 1, 2, 2, devices=survivors)


@pytest.mark.slow
def test_virtual_dp_degraded_step_bitwise_exact():
    """The algebraic core of elastic recovery: the (1, 1, 2, 2) step with
    virtual_dp=2 reproduces the (2, 1, 2, 2) step BITWISE — loss, grad
    norm, and every parameter — across three consecutive steps."""
    _need8()
    opt = make_optimizer("adamw", total_steps=TOTAL)
    mesh_full, make_full, step_full, _ = _setup(FULL, None, 1, opt)
    survivors = surviving_devices(mesh_full, "data")
    fact, fold = shrink_factorization(FULL, "data")
    _, make_deg, step_deg, _ = _setup(fact, survivors, fold, opt)

    sf, sd = make_full(), make_deg()
    for i in range(3):
        b = _batch(i)
        sf, mf = step_full(sf, b)
        sd, md = step_deg(sd, b)
        assert float(mf["loss"]) == float(md["loss"]), f"step {i}"
        assert float(mf["grad_norm"]) == float(md["grad_norm"]), f"step {i}"
    _assert_states_equal(sf, sd)


@pytest.mark.slow
def test_elastic_chaos_shrink_resumes_to_exact_golden(tmp_path):
    """The acceptance chaos test (ISSUE 10): a data-axis device slice dies
    at step 6; the supervisor shrinks (2,1,2,2) -> (1,1,2,2) over the four
    survivors, reshards the step-4 checkpoint, resumes with virtual_dp=2.
    Final fp32 loss and all params EXACTLY equal the fault-free run."""
    _need8()
    opt = make_optimizer("adamw", total_steps=TOTAL)

    def make_setup(fact, devices, vdp):
        return _setup(fact, devices, vdp, opt)

    d = str(tmp_path / "ckpt")
    plan = FaultPlan.parse("shrink=6:data")
    assert plan.shrink_at == ((6, "data"),)
    inj = FaultInjector(plan, None)        # supervisor rebinds per attempt
    loop_cfg = LoopConfig(total_steps=TOTAL, ckpt_dir=d, ckpt_every=4,
                          keep=5, log_every=1000)
    state, hist = elastic_restart_on_failure(
        make_setup, _make_iter, loop_cfg, factorization=FULL, injector=inj,
        backoff_base=0.01, logger=lambda *a: None)

    _, make_state, step, _ = make_setup(FULL, None, 1)
    golden, ghist = run(make_state(), step, _make_iter(0),
                        LoopConfig(total_steps=TOTAL, log_every=1000),
                        logger=lambda *a: None)

    assert hist[-1]["loss"] == ghist[-1]["loss"], "final fp32 loss must be EXACT"
    _assert_states_equal(state, golden)
    assert int(state["step"]) == TOTAL
    assert hist.health["restarts"] == 1
    assert hist.health["mesh_shrinks"] == 1


@pytest.mark.slow
def test_cross_mesh_restore_lands_in_golden_family(tmp_path):
    """A (2, 1, 2, 2) hybrid checkpoint resharded onto ONE device
    continues into the recorded golden loss family (tests/md/
    test_golden.py): step 1 on the full mesh, restore_resharded to the
    degenerate mesh, step 2 within rtol 1e-4 of the pinned value."""
    _need8()
    from repro.checkpoint import ckpt as ckpt_lib
    golden = (5.932372093200684, 5.708051681518555)   # hybrid_cp_2x1x2x2
    opt = make_optimizer("adamw", total_steps=10)
    key = jax.random.PRNGKey(1)
    b = {"tokens": jax.random.randint(key, (16, 16), 0, CFG.vocab_size),
         "labels": jax.random.randint(jax.random.fold_in(key, 1), (16, 16),
                                      0, CFG.vocab_size)}

    _, make_state, step, _ = _setup(FULL, None, 1, opt)
    s, m = step(make_state(), b)
    np.testing.assert_allclose(float(m["loss"]), golden[0], rtol=1e-4)
    ckpt_lib.save(str(tmp_path), 1, s)

    _, make1, step1, _ = _setup((1, 1, 1, 1, 1), [jax.devices()[0]], 1, opt)
    like = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), make1())
    restored, got = ckpt_lib.restore_resharded(str(tmp_path), None, like=like)
    assert got == 1
    _, m2 = step1(restored, b)
    np.testing.assert_allclose(float(m2["loss"]), golden[1], rtol=1e-4)


@pytest.mark.slow
def test_elastic_cli_end_to_end(tmp_path):
    """`--elastic` through the real CLI: a shrink fault mid-run must
    self-reshard (mesh_shrinks=1) and finish with the EXACT fault-free
    final fp32 loss (the done-line prints full float repr)."""
    _need8()
    import os
    import re
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    # the CLI turns the persistent compile cache on; tests keep it off
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(root, "src"),
               JAX_ENABLE_COMPILATION_CACHE="false")
    base = [sys.executable, "-m", "repro.launch.train", "--arch", "glm4-9b",
            "--reduced", "--hybrid-mesh", "2,1,2,2", "--microbatches", "4",
            "--steps", "8", "--batch", "16", "--seq", "64"]

    def final_loss(out):
        m = re.search(r"done: final loss ([0-9.e+-]+)", out)
        assert m, out
        return m.group(1)

    chaos = subprocess.run(
        base + ["--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-every", "4",
                "--fault-plan", "shrink=5:data", "--elastic"],
        capture_output=True, text=True, env=env, timeout=900)
    assert chaos.returncode == 0, chaos.stdout + chaos.stderr
    assert "mesh_shrinks=1" in chaos.stdout, chaos.stdout
    assert "virtual_dp=2" in chaos.stdout, chaos.stdout

    clean = subprocess.run(base, capture_output=True, text=True, env=env,
                           timeout=900)
    assert clean.returncode == 0, clean.stdout + clean.stderr
    assert final_loss(chaos.stdout) == final_loss(clean.stdout), (
        chaos.stdout + clean.stdout)


def test_device_loss_is_not_retried_as_plain_restart():
    """DeviceLossError fired by the injector carries the lost axis — the
    elastic supervisor's dispatch key."""
    plan = FaultPlan.parse("shrink=2:ctx")
    calls = []
    inj = FaultInjector(plan, lambda s, b: calls.append(s) or (s, {}))
    import jax.numpy as jnp
    state = {"step": jnp.int32(2)}
    with pytest.raises(DeviceLossError) as ei:
        inj(state, {})
    assert ei.value.axis == "ctx" and ei.value.step == 2
    inj(state, {})                         # fire-once: the replay runs clean
    assert len(calls) == 1
