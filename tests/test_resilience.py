"""Resilience: SPMD-consistent non-finite guard, fault injection, verified
recovery (DESIGN §9).

The headline property: under a fault plan combining a NaN-poisoned
gradient step, a crash, and bit-flip corruption of the newest checkpoint,
supervised training self-heals — skip, crash, quarantine + fallback
restore, replay — and the final fixed-seed state EXACTLY matches the
fault-free run (the 8-device hybrid sibling lives in
tests/md/test_resilience_md.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.data import DataConfig, SyntheticLM
from repro.models import init_params
from repro.optim import make_optimizer
from repro.resilience import (FaultInjector, FaultPlan, InjectedCrash,
                              corrupt_checkpoint, nan_grad_hook,
                              nonfinite_count, nonfinite_flag, tree_where)
from repro.train import (LoopConfig, NonFiniteStreakError, build_train_step,
                         elastic_restart_on_failure, init_train_state,
                         restart_on_failure, run)

TOTAL = 12


def _setup():
    cfg = reduced(get_config("phi4-mini-3.8b"))
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                  global_batch=8, seed=3))
    opt = make_optimizer("adamw", total_steps=TOTAL, base_lr=1e-3)

    def make_state():
        return init_train_state(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                                opt)

    def make_iter(start):
        class It:
            def __init__(self, s):
                self.s = s

            def __next__(self):
                s = self.s
                self.s += 1
                return s, data.batch(s)
        return It(start)

    return cfg, opt, data, make_state, make_iter


@pytest.fixture(scope="module")
def rig():
    cfg, opt, data, make_state, make_iter = _setup()
    step = jax.jit(build_train_step(cfg, None, opt))
    poisoned = jax.jit(build_train_step(cfg, None, opt,
                                        fault_hook=nan_grad_hook()))
    inf_poisoned = jax.jit(build_train_step(
        cfg, None, opt, fault_hook=nan_grad_hook(float("inf"))))
    return dict(cfg=cfg, opt=opt, data=data, make_state=make_state,
                make_iter=make_iter, step=step, poisoned=poisoned,
                inf_poisoned=inf_poisoned)


# ---------------------------------------------------------------------------
# guard primitives
# ---------------------------------------------------------------------------

def test_nonfinite_count_and_flag():
    clean = {"a": jnp.ones(3), "i": jnp.arange(3)}           # ints ignored
    assert int(nonfinite_count(clean)) == 0
    bad = {"a": jnp.array([1.0, jnp.nan, jnp.inf]), "i": jnp.arange(3)}
    assert int(nonfinite_count(bad)) == 2
    assert int(nonfinite_flag(bad)) == 1


def test_tree_where_selects_not_blends():
    # a blend (ok*new + (1-ok)*old) would propagate the rejected NaN
    new = {"w": jnp.array([jnp.nan, 2.0])}
    old = {"w": jnp.array([1.0, 1.0])}
    kept = tree_where(jnp.array(False), new, old)
    np.testing.assert_array_equal(np.asarray(kept["w"]), [1.0, 1.0])


# ---------------------------------------------------------------------------
# the guard inside the train step
# ---------------------------------------------------------------------------

def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("variant", ["poisoned", "inf_poisoned"])
def test_guard_skips_bitwise_and_recovers(rig, variant):
    """A NaN/Inf gradient step leaves params AND optimizer moments bitwise
    unchanged, increments skipped_steps, advances step; the next clean
    step proceeds normally."""
    state = rig["make_state"]()
    s1, m1 = rig["step"](state, rig["data"].batch(0))
    assert int(m1["skipped"]) == 0

    s2, m2 = rig[variant](s1, rig["data"].batch(1))
    assert int(m2["skipped"]) == 1
    _assert_trees_equal(s1["params"], s2["params"])
    _assert_trees_equal(s1["opt"], s2["opt"])
    assert int(s2["step"]) == int(s1["step"]) + 1   # batch was consumed
    assert int(s2["skipped_steps"]) == 1

    s3, m3 = rig["step"](s2, rig["data"].batch(2))
    assert int(m3["skipped"]) == 0
    assert int(s3["skipped_steps"]) == 1
    changed = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(s2["params"]),
                        jax.tree_util.tree_leaves(s3["params"])))
    assert changed, "clean step after a skip must update params"


def test_guard_is_inert_on_clean_steps(rig):
    """Guard on vs off: identical loss and identical params trajectory."""
    unguarded = jax.jit(build_train_step(rig["cfg"], None, rig["opt"],
                                         nonfinite_guard=False))
    sg, su = rig["make_state"](), rig["make_state"]()
    for i in range(2):
        b = rig["data"].batch(i)
        sg, mg = rig["step"](sg, b)
        su, mu = unguarded(su, b)
        assert float(mg["loss"]) == float(mu["loss"])
    _assert_trees_equal(sg["params"], su["params"])


# ---------------------------------------------------------------------------
# fault plan / injector
# ---------------------------------------------------------------------------

def test_fault_plan_parse():
    p = FaultPlan.parse("poison=3+4,crash=9,corrupt=truncate,slow=4:0.2,"
                        "seed=1,persistent")
    assert p.poison_grads_at == (3, 4)
    assert p.crash_at == (9,)
    assert p.corrupt_on_crash and p.corrupt_mode == "truncate"
    assert p.slow_at == (4,) and p.slow_seconds == 0.2
    assert p.seed == 1 and not p.once
    assert FaultPlan.parse("shrink=6:data").shrink_at == ((6, "data"),)
    assert (FaultPlan.parse("shrink=6:data+9:ctx").shrink_at
            == ((6, "data"), (9, "ctx")))
    with pytest.raises(ValueError):
        FaultPlan.parse("corrupt=scribble")
    with pytest.raises(ValueError):
        FaultPlan.parse("frobnicate=1")
    with pytest.raises(ValueError, match="step:axis"):
        FaultPlan.parse("shrink=6")


def test_injector_fire_once_semantics(rig):
    """A once-plan crash fires on the first pass over its step and never
    on the replay — the property the rollback/restore loop rests on."""
    plan = FaultPlan.parse("crash=1")
    inj = FaultInjector(plan, rig["step"])
    state = rig["make_state"]()
    s1, _ = inj(state, rig["data"].batch(0))
    with pytest.raises(InjectedCrash):
        inj(s1, rig["data"].batch(1))
    s2, _ = inj(s1, rig["data"].batch(1))      # replay: spent, runs clean
    assert int(s2["step"]) == 2


# ---------------------------------------------------------------------------
# self-healing end to end
# ---------------------------------------------------------------------------

def test_chaos_self_heals_to_exact_golden(rig, tmp_path):
    """poison@5 (guard skips) -> crash@9 corrupting the newest checkpoint
    (step 8, which embeds the skip) -> supervisor quarantines it, falls
    back to step 4 (pre-poison), replays with injection spent -> final
    params EXACTLY match the fault-free golden run."""
    d = str(tmp_path / "ckpt")
    plan = FaultPlan.parse("poison=5,crash=9,corrupt=bitflip")
    inj = FaultInjector(plan, rig["step"], poisoned_step_fn=rig["poisoned"],
                        ckpt_dir=d)
    loop_cfg = LoopConfig(total_steps=TOTAL, ckpt_dir=d, ckpt_every=4,
                          keep=5, log_every=1000)
    state, hist = restart_on_failure(
        rig["make_state"], inj, rig["make_iter"], loop_cfg,
        backoff_base=0.01, logger=lambda *a: None)

    golden, _ = run(rig["make_state"](), rig["step"], rig["make_iter"](0),
                    LoopConfig(total_steps=TOTAL, log_every=1000),
                    logger=lambda *a: None)
    _assert_trees_equal(state["params"], golden["params"])
    _assert_trees_equal(state["opt"], golden["opt"])
    assert int(state["step"]) == TOTAL
    assert hist.health["restarts"] == 1
    assert hist.health["quarantined_checkpoints"] == 1
    assert hist.health["skipped_steps"] == 1
    assert hist.health["backoff_seconds"] > 0


def test_nan_streak_rolls_back_and_advances_data(rig, tmp_path):
    """Consecutive skips past the threshold raise NonFiniteStreakError;
    the supervisor restores the last good checkpoint and advances the
    stateless data iterator past the poisoned window."""
    d = str(tmp_path / "ckpt")
    plan = FaultPlan.parse("poison=5+6")
    inj = FaultInjector(plan, rig["step"], poisoned_step_fn=rig["poisoned"],
                        ckpt_dir=d)
    loop_cfg = LoopConfig(total_steps=TOTAL, ckpt_dir=d, ckpt_every=4,
                          keep=5, log_every=1000, async_ckpt=False,
                          rollback_after_skips=2)
    logs = []
    state, hist = restart_on_failure(
        rig["make_state"], inj, rig["make_iter"], loop_cfg,
        backoff_base=0.01, logger=logs.append)
    assert hist.health["rollbacks"] == 1
    assert hist.health["skipped_steps"] == 2
    assert int(state["step"]) == TOTAL
    # rollback restored step 4 and skipped batches 5..6: offset = 3
    assert any("data_offset=3" in l for l in logs)


def test_streak_error_carries_window(rig):
    e = NonFiniteStreakError(5, 7, 3)
    assert (e.first_step, e.last_step, e.streak) == (5, 7, 3)


def test_unrecoverable_exception_propagates(rig, tmp_path):
    def bad_step(state, batch):
        raise TypeError("programming error, not a fault")
    loop_cfg = LoopConfig(total_steps=TOTAL, ckpt_dir=str(tmp_path / "c"),
                          log_every=1000)
    with pytest.raises(TypeError):
        restart_on_failure(rig["make_state"], bad_step, rig["make_iter"],
                           loop_cfg, backoff_base=0.01,
                           logger=lambda *a: None)


@pytest.mark.parametrize("elastic", [False, True])
def test_device_fault_propagates_without_restart(rig, tmp_path, elastic):
    """A device fault (out of memory, failed launch) is a RuntimeError,
    but replaying the step raises it again: it surfaces on the first
    occurrence, with no restart spent on it."""
    calls = []

    def oom_step(state, batch):
        calls.append(1)
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory")

    loop_cfg = LoopConfig(total_steps=TOTAL, ckpt_dir=str(tmp_path / "c"),
                          log_every=1000)
    with pytest.raises(jax.errors.JaxRuntimeError):
        if elastic:
            elastic_restart_on_failure(
                lambda fact, devices, vdp: (None, rig["make_state"],
                                            oom_step, None),
                rig["make_iter"], loop_cfg, factorization=(1, 1, 1, 1, 1),
                backoff_base=0.01, logger=lambda *a: None)
        else:
            restart_on_failure(rig["make_state"], oom_step, rig["make_iter"],
                               loop_cfg, backoff_base=0.01,
                               logger=lambda *a: None)
    assert len(calls) == 1


def test_corrupt_checkpoint_targets_named_array(rig, tmp_path):
    from repro.checkpoint import ckpt as ckpt_lib
    d = str(tmp_path)
    ckpt_lib.save(d, 1, {"params": {"w": jnp.arange(512.0)},
                         "step": jnp.int32(1)})
    fpath = corrupt_checkpoint(d, array="params/w", mode="bitflip", seed=7)
    assert fpath.endswith(".npy")
    with pytest.raises(ckpt_lib.CorruptCheckpointError):
        ckpt_lib.restore(d, like={"params": {"w": jnp.arange(512.0)},
                                  "step": jnp.int32(1)})
