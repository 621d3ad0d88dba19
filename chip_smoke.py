#!/usr/bin/env python3
"""Smoke test on the TPU: the repo's own entry points at published widths.

    python3 chip_smoke.py              # one chip: train + serve phi4-mini-3.8b
    python3 chip_smoke.py --chips 4    # four chips (v5e:2x2): hybrid executor

One chip.  ``repro.launch.train.main`` trains phi4-mini-3.8b at its
published widths (d_model 3072, 24 query / 8 KV heads of 128, d_ff 8192,
vocab 200064), cut to ``LAYERS`` layers, for ``STEPS`` steps of the
synthetic stream; every loss and grad norm must be finite with no restart
and no skipped step.  ``repro.launch.serve.main`` then serves the trained
parameters: ``BATCH`` requests of ``PROMPT``-token prompts, ``NEW_TOKENS``
greedy tokens, and every cached decode step's logits must agree with one
uncached forward pass over prompt + generated tokens.

Four chips.  The hybrid executor (``build_hybrid_train_step``) runs the
golden configurations of ``tests/md/test_golden.py`` in fp32 at (dp, S,
cp, tp, ep) = (1, 2, 1, 2, 1), (2, 1, 2, 1, 1) and, for the MoE config,
(1, 1, 1, 1, 4).  Each two-step loss pair must match the single-device
``build_train_step`` reference on one of the chips at rtol 1e-4, every
mesh must span all four chips, and every parameter sharded over pipe,
model or ep must keep its distinct shards on distinct chips.

Without a TPU the script exits non-zero before printing any result.  The
last line of stdout is one JSON object naming the device JAX reports.
Earlier lines carry sizes, ``memory_analysis`` and wall times; the times
include compilation and host work and are smoke timings, not metrics.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "phi4-mini-3.8b"
# Two layers at batch 4 x 512 is the depth and batch the v5e compile
# rehearsal fits with room (tests/test_tpu_compile.py): the tied 200064 x
# 3072 table alone is 7.4 GB of parameter, gradient and AdamW moments.
LAYERS, BATCH, SEQ, STEPS, LR = 2, 4, 512, 5, 1e-3
PROMPT, NEW_TOKENS = 128, 32
# bf16 keeps 8 significant bits (a step of 2^-8 = 0.4 %).  The cached and
# the uncached path round different intermediates to bf16 (cached K/V vs
# recomputed, one-query softmax vs blockwise) at each of the ~6 roundings
# of the residual stream (embedding, 2 layers x 2 sublayers, final norm),
# so they may differ by ~6 x 2^-8 = 2.3 % of the logit scale with no
# fault (a CPU rehearsal at d_model 1536 showed 1.0-1.4 %; in fp32 the
# two paths agree to 1e-6).  A cache fault (wrong slot, wrong position,
# stale entry) moves the logits by their whole scale.
LOGIT_RTOL = 3e-2
HBM_BYTES = 16 * 10**9          # one v5e chip
GOLDEN_RTOL = 1e-4              # tests/md/test_golden.py
# (golden config, (dp, S, cp, tp, ep), microbatches): stage boundaries and
# TP rings; data psums and the KV ring; the AllToAll dispatch.  The
# executor takes the MoE load-balance statistic per microbatch; one
# microbatch makes it the whole batch's, as in the single-device reference.
FOUR_CHIP_CASES = [("dense", (1, 2, 1, 2, 1), 4),
                   ("dense", (2, 1, 2, 1, 1), 4),
                   ("moe", (1, 1, 1, 1, 4), 1)]


def check(ok: bool, what: str):
    if not ok:
        raise RuntimeError(f"smoke check failed: {what}")


def tpu_devices():
    """The TPU devices, or exit non-zero: never fall back to the CPU."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.exit(f"chip_smoke: JAX found no backend: {e}")
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); this script runs on the chip only")
    return devices


def train_step_program():
    """The launcher's jitted single-device train step at the smoke's sizes,
    with the abstract ``(state, batch)`` it takes."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, with_layers
    from repro.launch.train import jit_step
    from repro.models import init_params
    from repro.optim import make_optimizer
    from repro.train import build_train_step, init_train_state

    cfg = with_layers(get_config(ARCH), LAYERS)
    opt = make_optimizer(cfg.optimizer, total_steps=STEPS, base_lr=LR)
    step = jit_step(build_train_step(cfg, None, opt))
    state = jax.eval_shape(
        lambda k: init_train_state(cfg, init_params(cfg, k), opt),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    tok = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)
    return cfg, step, state, {"tokens": tok, "labels": tok}


def device_bytes(memory_analysis) -> int:
    """Arguments + temporaries + outputs that do not alias an argument."""
    m = memory_analysis
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


def one_chip():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve as serve_launcher
    from repro.launch import train as train_launcher
    from repro.models import forward

    cfg, step, state_shapes, batch_shapes = train_step_program()
    n_params = sum(math.prod(l.shape) for l in
                   jax.tree_util.tree_leaves(state_shapes["params"]))
    print(f"config: {ARCH} d_model={cfg.d_model} heads={cfg.num_heads}/"
          f"{cfg.num_kv_heads}x{cfg.resolved_head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} tied={cfg.tie_embeddings} "
          f"dtype={cfg.dtype}")
    print(f"cut: depth {LAYERS} of 32 layers (widths as published); "
          f"{n_params / 1e6:.1f}M params; train batch {BATCH} x {SEQ}, "
          f"{STEPS} steps; serve {BATCH} requests x {PROMPT} prompt + "
          f"{NEW_TOKENS} new tokens")
    t0 = time.perf_counter()
    mem = step.lower(state_shapes, batch_shapes).compile().memory_analysis()
    print(f"train step memory_analysis: arguments "
          f"{mem.argument_size_in_bytes} B, temporaries "
          f"{mem.temp_size_in_bytes} B, outputs {mem.output_size_in_bytes} B,"
          f" aliased {mem.alias_size_in_bytes} B -> {device_bytes(mem)} B "
          f"on the device (compiled in {time.perf_counter() - t0:.1f} s)")
    check(mem.alias_size_in_bytes > 0, "train state is not donated")
    check(device_bytes(mem) < HBM_BYTES, "train step exceeds one chip's HBM")

    t0 = time.perf_counter()
    state, hist = train_launcher.main(
        ["--arch", ARCH, "--layers", str(LAYERS), "--batch", str(BATCH),
         "--seq", str(SEQ), "--steps", str(STEPS), "--lr", str(LR)])
    dt = time.perf_counter() - t0
    losses = [r["loss"] for r in hist]
    gnorms = [r["grad_norm"] for r in hist]
    print(f"train: losses {losses}")
    print(f"train: grad norms {gnorms}")
    print(f"train: smoke timing {dt:.1f} s for {len(hist)} steps incl. "
          f"compile; step seconds {[round(r['sec'], 4) for r in hist]}")
    check(len(hist) == STEPS, f"{len(hist)} steps recorded, not {STEPS}")
    check(all(np.isfinite(losses)) and all(np.isfinite(gnorms)),
          "non-finite loss or grad norm")
    check(hist.health["restarts"] == 0, f"restarts: {hist.health}")
    check(hist.health["skipped_steps"] == 0, f"skipped steps: {hist.health}")
    params = state["params"]
    del state                                  # free the optimizer moments

    t0 = time.perf_counter()
    out = serve_launcher.main(
        ["--arch", ARCH, "--layers", str(LAYERS), "--batch", str(BATCH),
         "--prompt-len", str(PROMPT), "--steps", str(NEW_TOKENS)],
        params=params)
    print(f"serve: smoke timing {time.perf_counter() - t0:.1f} s incl. "
          f"compile")
    seq = jnp.concatenate([out["prompt"], out["tokens"]], axis=1)
    full = jax.jit(lambda p, t: forward(p, {"tokens": t}, cfg, None,
                                        mode="train")[0])(params, seq)
    want = np.asarray(full[:, PROMPT - 1:].astype(jnp.float32))
    got = np.asarray(out["logits"].astype(jnp.float32))
    check(got.shape == want.shape == (BATCH, NEW_TOKENS + 1, cfg.vocab_size),
          f"logits shape {got.shape} vs {want.shape}")
    check(np.isfinite(got).all(), "non-finite decode logits")
    scale = np.abs(want).max(axis=-1, keepdims=True)
    rel = np.abs(got - want) / scale
    print(f"serve: cached vs uncached logits, max |diff| / max |logit| "
          f"per step: worst {rel.max():.3e} (tolerance {LOGIT_RTOL}); "
          f"logit scale {float(scale.mean()):.4f}")
    check(rel.max() <= LOGIT_RTOL, "cached decode logits disagree with the "
                                   "uncached forward")
    tokens = np.asarray(out["tokens"])
    check(np.array_equal(tokens, got[:, :-1].argmax(-1)),
          "greedy tokens are not the argmax of the engine's logits")
    top2 = np.sort(want[:, :-1], axis=-1)[..., -2:]
    clear = (top2[..., 1] - top2[..., 0]) > 2 * LOGIT_RTOL * scale[:, :-1, 0]
    agree = tokens == want[:, :-1].argmax(-1)
    print(f"serve: {int(clear.sum())} of {clear.size} tokens have a clear "
          f"top-1 margin; all of those match the uncached argmax: "
          f"{bool(agree[clear].all())}")
    check(agree[clear].all(), "a clear greedy choice differs from the "
                              "uncached forward's")


def golden_configs():
    """The fp32 configurations of ``tests/md/test_golden.py`` by kind, and
    its batch maker."""
    sys.path.insert(0, os.path.join(ROOT, "tests", "md"))
    import test_golden as golden
    return {"dense": golden.CFG, "moe": golden.MOE_CFG}, golden._batch


def hybrid_program(cfg, fact, microbatches, devices=None):
    """The hybrid executor's jitted step on the ``(dp, S, cp, tp, ep)`` mesh
    over ``devices``, with its mesh, optimizer and parameter specs."""
    import jax
    import jax.numpy as jnp

    from repro.core.compile import resolve_parts
    from repro.launch.mesh import make_hybrid_mesh
    from repro.models import init_pipeline_params, pipeline_param_parts
    from repro.optim import make_optimizer
    from repro.sharding import Policy
    from repro.train import build_hybrid_train_step

    mesh = make_hybrid_mesh(*fact, devices=devices)
    pol = Policy.for_mesh(mesh, explicit_tp=True)
    opt = make_optimizer("adamw", total_steps=10)
    shapes = jax.eval_shape(
        lambda k: init_pipeline_params(cfg, k, pol.pipe_size),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    specs = resolve_parts(pipeline_param_parts(cfg, pol, shapes), pol)
    step = jax.jit(build_hybrid_train_step(
        cfg, pol, opt, num_microbatches=microbatches, schedule="1f1b"))
    return mesh, opt, specs, step


def four_chips(devices):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding

    from repro.models import init_params, init_pipeline_params
    from repro.optim import make_optimizer
    from repro.train import build_train_step, init_train_state

    check(len(devices) == 4, f"{len(devices)} devices, not 4")
    configs, make_batch = golden_configs()
    batch = make_batch(jax.random.PRNGKey(1))

    def two_steps(step, state):
        losses = []
        for _ in range(2):
            state, metrics = step(state, batch)
            losses.append(float(jax.device_get(metrics["loss"])))
        return state, losses

    def reference(cfg):
        opt = make_optimizer("adamw", total_steps=10)
        state = init_train_state(
            cfg, init_params(cfg, jax.random.PRNGKey(0)), opt)
        return two_steps(jax.jit(build_train_step(cfg, None, opt)), state)[1]

    def hybrid(cfg, fact, microbatches):
        mesh, opt, specs, step = hybrid_program(cfg, fact, microbatches)
        check(mesh.devices.size == 4
              and set(mesh.devices.flat) == set(devices),
              f"mesh {fact} does not span the four chips")
        params = init_pipeline_params(cfg, jax.random.PRNGKey(0),
                                      mesh.shape["pipe"])
        params = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            params, specs)
        state, losses = two_steps(step, init_train_state(cfg, params, opt))
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        n_split = 0
        for (path, leaf), spec in zip(
                jax.tree_util.tree_leaves_with_path(state["params"]),
                jax.tree_util.tree_leaves(specs)):
            axes = {a for e in spec if e is not None
                    for a in (e if isinstance(e, tuple) else (e,))}
            split = axes & {"pipe", "model", "ep"}
            if not any(sizes[a] > 1 for a in split):
                continue
            shards = leaf.addressable_shards
            slices = {tuple((s.start, s.stop) for s in sh.index)
                      for sh in shards}
            want = math.prod(sizes[a] for a in axes)
            check(len({sh.device for sh in shards}) == 4
                  and len(slices) == want,
                  f"{fact} {jax.tree_util.keystr(path)}: {len(slices)} "
                  f"distinct shards over "
                  f"{len({sh.device for sh in shards})} chips, want {want} "
                  f"over 4")
            n_split += 1
        print(f"hybrid {fact}: mesh {dict(sizes)} over devices "
              f"{[d.id for d in mesh.devices.flat]}; {n_split} sharded "
              f"parameter leaves on distinct chips")
        return losses

    with jax.default_matmul_precision("highest"):
        refs = {kind: reference(cfg) for kind, cfg in configs.items()}
        print(f"single-device reference on {devices[0]}: {refs}")
        for kind, fact, m in FOUR_CHIP_CASES:
            t0 = time.perf_counter()
            losses = hybrid(configs[kind], fact, m)
            print(f"hybrid {fact} {kind}: losses {losses} vs reference "
                  f"{refs[kind]} (smoke timing "
                  f"{time.perf_counter() - t0:.1f} s incl. compile)")
            check(np.allclose(losses, refs[kind], rtol=GOLDEN_RTOL, atol=0),
                  f"{fact} losses {losses} != reference {refs[kind]}")
            check(losses[1] < losses[0], f"{fact} did not learn")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train + serve phi4-mini-3.8b on one chip; "
                         "4: the hybrid executor on a v5e:2x2 host")
    args = ap.parse_args(argv)
    devices = tpu_devices()

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    print(f"devices: {devices}")
    if args.chips == 4:
        four_chips(devices)
    else:
        one_chip()
    d = devices[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind,
                                             "count": len(devices)}}))


if __name__ == "__main__":
    main()
