"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch glm4-9b --reduced \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Hybrid DP x pipe x ctx x tensor x expert (DESIGN §5-6, §8) — any
(dp, pp, cp, tp, ep) factorization of the visible devices; cp > 1 turns on
ring-attention context parallelism (the sequence is sharded over the ctx
axis and KV shards rotate, so no device ever holds the full sequence);
ep > 1 turns on expert parallelism for MoE archs (tokens dispatch to
expert shards over the ep axis via AllToAll):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.train --arch glm4-9b --reduced \
        --hybrid-mesh 2,1,2,2 --microbatches 4 --steps 20 --batch 16

On a CPU host use --reduced (tiny same-family config).  On a TPU drop it
for the published widths; ``--layers N`` cuts the depth to N layers (the
only cut: widths stay published), e.g. phi4-mini-3.8b on one v5e chip:

    PYTHONPATH=src python -m repro.launch.train --arch phi4-mini-3.8b \
        --layers 2 --batch 4 --seq 512 --steps 5

The step is jitted with the train state donated, so the old and the new
state never occupy device memory together.  The loop is the fault-
tolerant one from train/loop.py (atomic checkpoints, auto-resume,
straggler monitor, SPMD-consistent non-finite skip).  ``--fault-plan``
turns on the deterministic chaos harness (resilience/inject.py) — e.g.
``--fault-plan poison=5,crash=9,corrupt=bitflip`` NaN-poisons step 5's
gradients (the guard skips), crashes at step 9 bit-flipping the newest
checkpoint, and the supervisor quarantines it, falls back, and resumes.
"""

from __future__ import annotations

import argparse
import dataclasses
from functools import partial

import jax

from repro.configs import ARCH_IDS, get_config, reduced, with_layers
from repro.data import DataConfig, PrefetchIterator, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_hybrid_mesh
from repro.models import init_params, init_pipeline_params
from repro.optim import make_optimizer
from repro.resilience import FaultInjector, FaultPlan, nan_grad_hook
from repro.sharding import Policy
from repro.train import (LoopConfig, build_hybrid_train_step,
                         build_train_step, elastic_restart_on_failure,
                         init_train_state, restart_on_failure)


# Every train step takes the state as argument 0 and returns its successor:
# donating it lets the update write in place.  No caller reads a state after
# passing it to a step (the loop rebinds it, async checkpoints snapshot to
# the host before the next step, fault injection raises before the step).
jit_step = partial(jax.jit, donate_argnums=(0,))


def main(argv=None):
    """Train with the command line ``argv`` (default ``sys.argv[1:]``);
    returns ``(state, history)`` from the supervised loop."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="glm4-9b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers (a multiple of "
                         "the arch's block period); widths stay as "
                         "configured")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--hybrid-mesh", default=None, metavar="DP,PP,CP,TP,EP",
                    help="run the hybrid executor on a (data, pipe, ctx, "
                         "model, ep) mesh with this factorization; CP is "
                         "the ring-attention context-parallel degree, EP "
                         "the MoE expert-parallel degree (a 4-value "
                         "DP,PP,CP,TP form is accepted with EP=1, a "
                         "3-value DP,PP,TP form with CP=EP=1)")
    ap.add_argument("--microbatches", type=int, default=4,
                    help="pipeline microbatches per step (hybrid mesh only)")
    ap.add_argument("--schedule", default="1f1b",
                    choices=("1f1b", "fill_drain"))
    ap.add_argument("--use-flash", action="store_true",
                    help="route train attention through kernels.ops."
                         "flash_attention (the compiled Pallas kernel on a "
                         "TPU, the interpreter elsewhere); GSPMD path only")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="inject deterministic faults (resilience/inject.py)"
                         ": comma-separated tokens, e.g. 'poison=5,crash=9,"
                         "corrupt=bitflip,slow=4:0.2,seed=1'; keys: poison "
                         "(NaN gradients at steps, '+'-joined), value "
                         "(nan/inf), crash, corrupt (bitflip|truncate the "
                         "newest checkpoint on crash), array (corrupt "
                         "target key substring), slow (step:seconds), "
                         "seed, persistent (faults re-fire on replay)")
    ap.add_argument("--rollback-after-skips", type=int, default=None,
                    help="NaN-streak threshold: after this many consecutive "
                         "guard-skipped steps, roll back to the last good "
                         "checkpoint and advance the data stream past the "
                         "poisoned window")
    ap.add_argument("--elastic", action="store_true",
                    help="mesh-shrinking supervision (DESIGN §10): on a "
                         "simulated device loss (fault-plan key "
                         "'shrink=step:axis') shrink to the largest legal "
                         "degraded factorization, reshard the newest "
                         "verified checkpoint through the Repartition "
                         "plan, fold lost data parallelism into grad "
                         "accumulation (loss-exact), resume; requires "
                         "--hybrid-mesh")
    ap.add_argument("--max-restarts", type=int, default=3)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.layers is not None:
        cfg = with_layers(cfg, args.layers)
    n_dev = len(jax.devices())
    hybrid = None
    if args.hybrid_mesh:
        parts = [int(x) for x in args.hybrid_mesh.split(",")]
        if len(parts) == 3:          # legacy DP,PP,TP form
            parts = parts[:2] + [1] + parts[2:]
        if len(parts) == 4:          # DP,PP,CP,TP form
            parts = parts + [1]
        if len(parts) != 5:
            raise SystemExit("--hybrid-mesh wants DP,PP,CP,TP,EP "
                             "(or DP,PP,CP,TP / DP,PP,TP)")
        dp, pp, cp, tp, ep = parts
        if dp * pp * cp * tp * ep != n_dev:
            raise SystemExit(
                f"--hybrid-mesh {dp}x{pp}x{cp}x{tp}x{ep} != {n_dev} devices")
        if args.seq % cp:
            raise SystemExit(f"--seq {args.seq} not divisible by CP={cp}")
        if ep > 1 and (cfg.num_experts or 0) % ep:
            raise SystemExit(f"--hybrid-mesh EP={ep} does not divide "
                             f"num_experts={cfg.num_experts or 0} "
                             f"for --arch {args.arch}")
        if args.use_flash:
            raise SystemExit("--use-flash is GSPMD-only: the pipeline/ctx "
                             "executor owns attention dispatch")
        hybrid = (dp, pp, cp, tp, ep)
        mesh = make_hybrid_mesh(dp, pp, cp, tp, ep)
        policy = Policy.for_mesh(mesh, explicit_tp=tp > 1)
    else:
        mesh = make_host_mesh((n_dev, 1))
        policy = Policy(mesh=mesh) if n_dev > 1 else None

    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                                  global_batch=args.batch, seed=args.seed))
    opt = make_optimizer(cfg.optimizer, total_steps=args.steps,
                         base_lr=args.lr)
    cfg = dataclasses.replace(cfg, grad_accum=1)
    plan = FaultPlan.parse(args.fault_plan) if args.fault_plan else None

    def make_iter(start):
        return PrefetchIterator(data, start_step=start)

    loop_cfg = LoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                          ckpt_every=args.ckpt_every, log_every=10,
                          rollback_after_skips=args.rollback_after_skips)

    if args.elastic:
        if not hybrid:
            raise SystemExit("--elastic requires --hybrid-mesh")
        hook = nan_grad_hook(plan.poison_value) if plan is not None else None

        def make_setup(fact, devices, vdp):
            dp, pp, cp, tp, ep = fact
            m = make_hybrid_mesh(dp, pp, cp, tp, ep, devices=devices)
            pol = Policy.for_mesh(m, explicit_tp=tp > 1)
            kw = dict(num_microbatches=args.microbatches,
                      schedule=args.schedule, virtual_dp=vdp)
            s = jit_step(build_hybrid_train_step(cfg, pol, opt, **kw))
            p = (jit_step(build_hybrid_train_step(cfg, pol, opt,
                                                  fault_hook=hook, **kw))
                 if hook is not None else None)

            def mk():
                params = init_pipeline_params(
                    cfg, jax.random.PRNGKey(args.seed), pol.pipe_size)
                n = sum(l.size for l in jax.tree_util.tree_leaves(params))
                print(f"{args.arch}: {n/1e6:.1f}M params, mesh={m.shape}, "
                      f"virtual_dp={vdp}")
                return init_train_state(cfg, params, opt)

            return m, mk, s, p

        injector = (FaultInjector(plan, None, ckpt_dir=args.ckpt_dir)
                    if plan is not None else None)
        state, hist = elastic_restart_on_failure(
            make_setup, make_iter, loop_cfg, factorization=hybrid,
            injector=injector, max_restarts=args.max_restarts)
        health = " ".join(f"{k}={v:.2f}" if isinstance(v, float)
                          else f"{k}={v}" for k, v in hist.health.items())
        print(f"done: final loss {hist[-1]['loss']!r} over {len(hist)} "
              f"steps  [{health}]")
        return state, hist

    if hybrid:
        step = jit_step(build_hybrid_train_step(
            cfg, policy, opt, num_microbatches=args.microbatches,
            schedule=args.schedule))
    else:
        step = jit_step(build_train_step(cfg, policy, opt,
                                         use_flash=args.use_flash))
    if plan is not None:
        # the poisoned sibling is a second compiled variant of the SAME
        # builder with the gradient fault hook traced in; the injector
        # chooses between them on the host (fire-once across restarts)
        hook = nan_grad_hook(plan.poison_value)
        if hybrid:
            poisoned = jit_step(build_hybrid_train_step(
                cfg, policy, opt, num_microbatches=args.microbatches,
                schedule=args.schedule, fault_hook=hook))
        else:
            poisoned = jit_step(build_train_step(
                cfg, policy, opt, use_flash=args.use_flash, fault_hook=hook))
        step = FaultInjector(plan, step, poisoned_step_fn=poisoned,
                             ckpt_dir=args.ckpt_dir)

    def make_state():
        if hybrid:
            params = init_pipeline_params(cfg, jax.random.PRNGKey(args.seed),
                                          policy.pipe_size)
        else:
            params = init_params(cfg, jax.random.PRNGKey(args.seed))
        n = sum(l.size for l in jax.tree_util.tree_leaves(params))
        print(f"{args.arch}: {n/1e6:.1f}M params, mesh={mesh.shape}")
        return init_train_state(cfg, params, opt)

    state, hist = restart_on_failure(make_state, step, make_iter, loop_cfg,
                                     max_restarts=args.max_restarts)
    health = " ".join(f"{k}={v:.2f}" if isinstance(v, float) else f"{k}={v}"
                      for k, v in hist.health.items())
    print(f"done: final loss {hist[-1]['loss']!r} over {len(hist)} steps  "
          f"[{health}]")
    return state, hist


if __name__ == "__main__":
    main()
