"""Training loop with fault tolerance and straggler monitoring.

Restart contract (1000-node posture): all state needed to resume —
parameters, optimizer moments, step counter, skipped-step count — is in
the checkpoint; the data pipeline is stateless-addressable by step.
``run`` therefore resumes exactly after any crash by restoring the newest
*verified* checkpoint, and ``restart_on_failure`` wraps the step loop in a
supervised retry (the in-process analogue of a cluster controller
rescheduling a failed job): a declared set of recoverable exception types,
jittered exponential backoff, fallback past corrupt checkpoints
(quarantined as ``.corrupt``), and NaN-streak rollback — when the
SPMD-consistent guard (DESIGN §9) skips ``rollback_after_skips`` steps in
a row the poison is persistent, so the supervisor restores the last good
checkpoint and advances the stateless data iterator past the poisoned
window (``data_offset``: batch ``step + offset`` feeds step ``step``).

Straggler mitigation: an EWMA step-time monitor flags steps slower than
``straggler_factor`` x the moving average (input stalls, collective jams);
the data pipeline prefetches in the background so slow hosts don't
serialize, and slow-step counts are surfaced in metrics for the operator.

Health accounting: ``run``/``restart_on_failure`` return a
:class:`History` — a list of per-step records whose ``.health`` dict
carries the structured counters (restarts, rollbacks, skipped/slow steps,
backoff seconds, quarantined checkpoints) an operator would page on.
"""

from __future__ import annotations

import random as _random
import time
from dataclasses import dataclass

import jax

from repro.checkpoint import ckpt as ckpt_lib


class History(list):
    """Per-step records plus structured health counters in ``.health``."""

    def __init__(self, *a):
        super().__init__(*a)
        self.health = {"restarts": 0, "rollbacks": 0, "skipped_steps": 0,
                       "slow_steps": 0, "backoff_seconds": 0.0,
                       "quarantined_checkpoints": 0, "mesh_shrinks": 0}


class NonFiniteStreakError(RuntimeError):
    """The guard skipped ``streak`` consecutive steps: the poison is
    persistent (bad data window, diverged state), not a transient burst —
    skip-and-continue would spin forever.  Carries the window so the
    supervisor can roll back and advance the data stream past it."""

    def __init__(self, first_step: int, last_step: int, streak: int):
        super().__init__(
            f"non-finite gradients for {streak} consecutive steps "
            f"({first_step}..{last_step})")
        self.first_step, self.last_step, self.streak = first_step, last_step, streak


@dataclass
class StragglerMonitor:
    alpha: float = 0.1
    factor: float = 1.5
    ewma: float | None = None
    slow_steps: int = 0

    def observe(self, dt: float) -> bool:
        slow = self.ewma is not None and dt > self.factor * self.ewma
        self.ewma = dt if self.ewma is None else (1 - self.alpha) * self.ewma + self.alpha * dt
        if slow:
            self.slow_steps += 1
        return slow


@dataclass
class LoopConfig:
    total_steps: int = 100
    ckpt_dir: str | None = None
    ckpt_every: int = 50
    keep: int = 3
    log_every: int = 10
    async_ckpt: bool = True
    fail_at_step: int | None = None      # legacy injection hook (resilience/inject.py generalizes)
    rollback_after_skips: int | None = None  # NaN-streak rollback threshold


def run(state, train_step, data_iter, loop_cfg: LoopConfig, *, logger=print,
        history: History | None = None, data_offset: int = 0):
    """Run the step loop from ``state``; returns (state, history).

    ``data_offset`` shifts the stateless data addressing: step ``i``
    consumes batch ``i + data_offset`` — 0 except after a NaN-streak
    rollback advanced the iterator past a poisoned window.  ``history``
    lets the supervisor thread one :class:`History` through restarts.
    """
    monitor = StragglerMonitor()
    if history is None:
        history = History()
    start = int(jax.device_get(state["step"]))
    streak_first = None
    streak = 0
    for step in range(start, loop_cfg.total_steps):
        data_step, batch = next(data_iter)
        assert data_step == step + data_offset, (data_step, step, data_offset)
        t0 = time.perf_counter()
        if loop_cfg.fail_at_step is not None and step == loop_cfg.fail_at_step:
            raise RuntimeError(f"injected fault at step {step}")
        state, metrics = train_step(state, batch)
        jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        slow = monitor.observe(dt)
        rec = {k: float(jax.device_get(v)) for k, v in metrics.items()}
        rec.update(step=step, sec=dt, slow=slow)
        history.append(rec)
        history.health["slow_steps"] += slow
        skipped = bool(rec.get("skipped", 0.0))
        if skipped:
            history.health["skipped_steps"] += 1
            streak_first = step if streak == 0 else streak_first
            streak += 1
            logger(f"step {step:5d}  non-finite gradients: step SKIPPED "
                   f"(streak {streak})")
            if (loop_cfg.rollback_after_skips
                    and streak >= loop_cfg.rollback_after_skips):
                raise NonFiniteStreakError(streak_first, step, streak)
        else:
            streak = 0
        if step % loop_cfg.log_every == 0 or slow:
            extra = ""
            if "bubble_fraction" in rec:
                # pipeline-parallel steps report their schedule's bubble
                extra = f"  bubble {rec['bubble_fraction']:.2f}"
            logger(f"step {step:5d}  loss {rec['loss']:.4f}  "
                   f"gnorm {rec['grad_norm']:.3f}  {dt*1e3:.0f} ms" + extra
                   + ("  [STRAGGLER]" if slow else ""))
        if (loop_cfg.ckpt_dir and loop_cfg.ckpt_every
                and (step + 1) % loop_cfg.ckpt_every == 0):
            saver = (ckpt_lib.save_async if loop_cfg.async_ckpt else ckpt_lib.save)
            saver(loop_cfg.ckpt_dir, step + 1, state, keep=loop_cfg.keep)
    ckpt_lib.wait_pending()
    return state, history


# The declared recoverable surface: planned crashes/preemptions and loop
# faults (RuntimeError covers InjectedCrash + the legacy fail_at_step
# hook), I/O flakes around checkpoint storage (OSError), and host-side
# float traps.  Programming errors (TypeError, ValueError, KeyError...)
# stay fatal — restarting can't fix those and the retry would loop.
RECOVERABLE = (RuntimeError, OSError, FloatingPointError)
# Device faults (out of memory, a failed launch or compile) are
# RuntimeErrors too, but the same step on the same device raises them
# again: they propagate at once instead of burning every restart.
FATAL = (jax.errors.JaxRuntimeError,)


def restart_on_failure(make_state, train_step, make_data_iter,
                       loop_cfg: LoopConfig, *, shardings=None,
                       max_restarts: int = 3, recoverable=RECOVERABLE,
                       backoff_base: float = 0.5, backoff_max: float = 30.0,
                       backoff_jitter: float = 0.1, seed: int = 0,
                       logger=print, sleep=time.sleep):
    """Supervised retry loop: the single-process analogue of cluster restart.

    On a recoverable failure: restore the newest checkpoint that passes
    verification (corrupt ones are quarantined as ``.corrupt`` and the
    previous intact one is used — DESIGN §9), back off with seeded jittered
    exponential delay (``backoff_base * 2^k``, capped at ``backoff_max`` —
    the thundering-herd posture even though in-process), and resume.  On a
    :class:`NonFiniteStreakError` (persistent poison): additionally advance
    the stateless data iterator past the poisoned window via
    ``data_offset``.  Raises after ``max_restarts`` recoveries; exception
    types outside ``recoverable``, and device faults (``FATAL``),
    propagate immediately.  Returns
    ``(state, history)``, ``history.health`` carrying restart/rollback/
    skip/backoff/quarantine counters across all attempts.
    """
    rng = _random.Random(seed)
    history = History()
    restarts = 0
    data_offset = 0
    while True:
        state = make_state()
        start = 0
        if loop_cfg.ckpt_dir:
            got = ckpt_lib.restore_latest_verified(
                loop_cfg.ckpt_dir, like=state, shardings=shardings,
                logger=logger)
            if got is not None:
                state, start, quarantined = got
                history.health["quarantined_checkpoints"] += len(quarantined)
                logger(f"resumed from checkpoint step {start}"
                       + (f" (quarantined corrupt: {quarantined})"
                          if quarantined else ""))
        data_iter = make_data_iter(start + data_offset)
        try:
            return run(state, train_step, data_iter, loop_cfg, logger=logger,
                       history=history, data_offset=data_offset)
        except NonFiniteStreakError as e:
            restarts += 1
            history.health["rollbacks"] += 1
            # the poisoned data window is [first skipped batch, last skipped
            # batch]; replay model state from the last good checkpoint but
            # feed it the batches AFTER the window (stateless addressing
            # makes this a pure index shift)
            data_offset = max(data_offset, e.last_step + 1 + data_offset
                              - _restart_point(loop_cfg))
            logger(f"persistent non-finite streak: {e}; rolling back with "
                   f"data_offset={data_offset} "
                   f"(restart {restarts}/{max_restarts})")
            if restarts >= max_restarts:
                raise
        except FATAL:
            raise
        except recoverable as e:
            restarts += 1
            history.health["restarts"] += 1
            logger(f"failure: {e}; restart {restarts}/{max_restarts}")
            if restarts >= max_restarts:
                raise
            if loop_cfg.fail_at_step is not None:
                loop_cfg.fail_at_step = None      # injected faults fire once
        delay = min(backoff_max, backoff_base * (2 ** (restarts - 1)))
        delay *= 1.0 + backoff_jitter * rng.random()
        history.health["backoff_seconds"] += delay
        sleep(delay)


def _restart_point(loop_cfg: LoopConfig) -> int:
    """The step the next attempt will resume from (newest intact ckpt)."""
    if loop_cfg.ckpt_dir:
        return ckpt_lib.latest_step(loop_cfg.ckpt_dir) or 0
    return 0


def elastic_restart_on_failure(make_setup, make_data_iter,
                               loop_cfg: LoopConfig, *, factorization,
                               injector=None, max_restarts: int = 3,
                               recoverable=RECOVERABLE,
                               backoff_base: float = 0.5,
                               backoff_max: float = 30.0,
                               backoff_jitter: float = 0.1, seed: int = 0,
                               logger=print, sleep=time.sleep):
    """Mesh-shrinking supervisor: survives the permanent loss of devices.

    Extends :func:`restart_on_failure`'s restore-and-retry posture to
    :class:`~repro.resilience.inject.DeviceLossError` — the fault a plain
    restart cannot fix, because the lost devices never come back.  On a
    device loss the supervisor instead (DESIGN §10):

    1. drops the lost slice (``launch/mesh.surviving_devices``) and picks
       the largest legal degraded factorization
       (``launch/mesh.shrink_factorization``);
    2. folds lost DATA parallelism into gradient accumulation
       (``virtual_dp`` x= fold) so the global batch schedule — and, by the
       explicit-reduction-tree construction in core/pipeline.py, the fp32
       loss and every gradient — is BITWISE unchanged;
    3. rebuilds mesh/state/step via ``make_setup`` (rebinding a shared
       :class:`~repro.resilience.inject.FaultInjector` so fire-once faults
       stay spent), reshards the newest VERIFIED checkpoint onto the
       degraded mesh through the ``Repartition`` plan
       (``restore_latest_verified(..., reshard=True)``), and resumes.

    ``make_setup(factorization, devices, virtual_dp)`` returns
    ``(mesh, make_state, step_fn, poisoned_step_fn)`` (the last may be
    None); ``devices=None`` means the full device set.  Other recoverable
    failures restart on the CURRENT (possibly already degraded) mesh.
    Health adds ``mesh_shrinks`` to the usual counters.
    """
    from repro.launch.mesh import shrink_factorization, surviving_devices
    from repro.resilience.inject import DeviceLossError

    rng = _random.Random(seed)
    history = History()
    restarts = 0
    data_offset = 0
    fact = tuple(factorization)
    devices = None
    vdp = 1
    while True:
        mesh, make_state, step_fn, poisoned = make_setup(fact, devices, vdp)
        train_step = (injector.rebind(step_fn, poisoned)
                      if injector is not None else step_fn)
        state = make_state()
        start = 0
        if loop_cfg.ckpt_dir:
            got = ckpt_lib.restore_latest_verified(
                loop_cfg.ckpt_dir, like=state, reshard=True, logger=logger)
            if got is not None:
                state, start, quarantined = got
                history.health["quarantined_checkpoints"] += len(quarantined)
                logger(f"resumed from checkpoint step {start}"
                       + (f" (quarantined corrupt: {quarantined})"
                          if quarantined else ""))
        data_iter = make_data_iter(start + data_offset)
        try:
            return run(state, train_step, data_iter, loop_cfg, logger=logger,
                       history=history, data_offset=data_offset)
        except DeviceLossError as e:
            restarts += 1
            history.health["restarts"] += 1
            history.health["mesh_shrinks"] += 1
            survivors = surviving_devices(mesh, e.axis)
            fact, fold = shrink_factorization(fact, e.axis)
            if e.axis == "data":
                vdp *= fold
            want = 1
            for f in fact:
                want *= f
            devices = survivors[:want]
            logger(f"device loss on axis {e.axis!r}: shrinking to "
                   f"(dp, S, cp, tp, ep) = {fact} over {len(devices)} "
                   f"device(s), virtual_dp={vdp} "
                   f"(restart {restarts}/{max_restarts})")
            if restarts >= max_restarts:
                raise
        except FATAL:
            raise
        except recoverable as e:
            restarts += 1
            history.health["restarts"] += 1
            logger(f"failure: {e}; restart {restarts}/{max_restarts}")
            if restarts >= max_restarts:
                raise
        delay = min(backoff_max, backoff_base * (2 ** (restarts - 1)))
        delay *= 1.0 + backoff_jitter * rng.random()
        history.health["backoff_seconds"] += delay
        sleep(delay)
