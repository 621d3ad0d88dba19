"""Driver for serving cells: the program's ServeEngine, lockstep batches
in a closed loop.

Set-up makes the benchmark's seeded weights with the reference of the
configuration's family (``bench/family.py``), builds one
``repro.serve.ServeEngine`` over them and warms its programs with one
batch of the cell's shapes (prefill, two decode steps, the greedy pick).
The window then submits lockstep batches one after another, each as soon
as the last finished (the engine has no admission, so the load is a fixed
number of requests in flight, not an arrival rate): ``ServeEngine.prefill``
and ``ServeEngine.decode_step``, with the program's greedy pick, and every
step's tokens fetched to the host as a streaming server must.  A batch the
window's end cuts short counts the tokens it delivered.

Traffic parameters (``bench/traffic/<mix>.json``): ``batch`` requests
per lockstep batch, ``prompt`` tokens each (uniform over the vocabulary),
``new_tokens`` greedy tokens each, ``max_seq`` cache slots.  The cell's
file (``bench/workloads/<cell>.json``) holds ``check.requests`` and the
``limits`` of the comparison.  The record carries ``max_seq``, so that
``bench/scopes.py`` compiles the engine's programs at the cell's slots.

Correctness: once the window has closed and the engine is freed, a sample
of ``check.requests`` finished requests, drawn from the seed, is run
through the plain reference over prompt plus served tokens, and the widest
gap between the reference's best logit and a served token's is compared.

The ``window:`` line on standard error says where a run's time went: the
batches, the token gaps, the longest prefill and the backend compiles that
fell inside the window (none, once warm-up is complete), where the program
logs its compiles.
"""

from __future__ import annotations

import gc
import sys
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, family, generator

try:
    from repro.launch.compile_cache import compiles, log_compiles
except ImportError:           # a program from before its compile log
    compiles = log_compiles = None

# Each call's span reaches until its tokens are on the host, so the
# device work it launched lies inside it; the fetch is nested in it.
SPANS = ("prefill", "decode", "token_fetch")
SAMPLE_SALT = 0x5A3


def run(cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, t0: float, calibrate: str = ""):
    from repro.serve import ServeEngine

    ref, prog = family.load(config)
    cfg = prog.program_config(config)
    spec = ref.Spec.from_config(config)
    t = traffic
    B, P, N = t["batch"], t["prompt"], t["new_tokens"]
    if P + N > t["max_seq"]:
        raise ValueError(f"{P} + {N} tokens do not fit {t['max_seq']} slots")
    device = jax.devices()[0]
    key = generator.weights_key(seed)
    make = jax.jit(lambda k: ref.init_weights(spec, k))

    if log_compiles is not None:
        log_compiles()
    engine = ServeEngine(cfg, prog.to_program(make(key)), None,
                         max_seq=t["max_seq"], batch_size=B)

    def pick(logits):
        return ServeEngine._pick(logits, True, None, 1.0, 0)

    def serve(index, n, deadline, on):
        """One lockstep batch: up to ``n`` tokens per request, stopping
        at ``deadline``; returns its submission time, the host time of
        each step's tokens and the tokens (B, steps)."""
        prompt = generator.prompts(seed, index, B, P, cfg.vocab_size)
        submit = time.perf_counter()
        with common.span(on, "prefill"):
            logits, cache = engine.prefill(jnp.asarray(prompt))
            nxt = pick(logits)
            with common.span(on, "token_fetch"):
                toks = [np.asarray(nxt)]
        times = [time.perf_counter()]
        for k in range(1, n):
            if times[-1] >= deadline:
                break
            with common.span(on, "decode"):
                logits, cache = engine.decode_step(cache, nxt, jnp.int32(P + k - 1))
                nxt = pick(logits)
                with common.span(on, "token_fetch"):
                    toks.append(np.asarray(nxt))
            times.append(time.perf_counter())
        return {"prompt": prompt, "submit": submit, "times": times,
                "tokens": np.concatenate(toks, axis=1)}

    serve(0, 3, float("inf"), False)              # compile and warm
    setup_s = time.perf_counter() - t0

    out, batches = {}, []
    with common.profiled(trace, SPANS, out):
        start = time.perf_counter()
        deadline = start + seconds
        while time.perf_counter() < deadline:
            batches.append(serve(len(batches) + 1, N, deadline, trace))
    end = batches[-1]["times"][-1]
    window_s = end - start
    gaps = np.concatenate([np.diff(b["times"]) for b in batches])
    if compiles is None:
        compiled = "not logged"
    else:
        inside = [c for c in compiles() if c[2] > start and c[1] < end]
        compiled = f"{len(inside)}" + "".join(
            f"; {name} at {s - start:.3f} s, {e - s:.3f} s"
            for name, s, e in inside)
    # Printed so that a run reading far off shows where its time went.
    print(f"window: {len(batches)} batches, {window_s:.3f} s; token gaps "
          f"median {np.median(gaps) * 1e3:.2f} ms, longest "
          f"{gaps.max(initial=0) * 1e3:.2f} ms; longest prefill "
          f"{max(b['times'][0] - b['submit'] for b in batches) * 1e3:.2f} ms; "
          f"compiles in the window: {compiled}", file=sys.stderr)
    peak = common.peak_bytes(device)
    del engine
    gc.collect()

    finished = [b for b in batches if len(b["times"]) == N]
    rng = np.random.default_rng((seed % generator.SEED_RANGE, SAMPLE_SALT))
    pool = [(b, r) for b in range(len(finished)) for r in range(B)]
    chosen = rng.choice(len(pool), min(cell["check"]["requests"], len(pool)),
                        replace=False)
    w = make(key)
    widest, control = [], []
    for i in sorted(chosen):
        b, r = pool[i]
        served = finished[b]["tokens"][r]
        seq = np.concatenate([finished[b]["prompt"][r], served[:-1]])[None]
        gap, _ = ref.gaps_and_top(spec, "fp32", w, seq, served[None])
        widest.append(float(gap.max()))
        if calibrate == "control":
            _, low = ref.gaps_and_top(spec, "fp8", w, seq, served[None])
            gap, _ = ref.gaps_and_top(spec, "fp32", w, seq, low)
            control.append(float(gap.max()))
    numbers = {"max_gap": max(widest) if widest else None}
    correct, checks = common.judge(numbers, cell["limits"])
    rec = SimpleNamespace(
        config=config, cell=cell, device=device,
        setup_s=setup_s, window_s=window_s, trace=out.get("trace"),
        batch=B, prompt=P, new_tokens=N, max_seq=t["max_seq"], batches=batches,
        tokens=sum(B * len(b["times"]) for b in batches),
        attempted=B * len(batches), failed=0, compared=len(widest),
        correct=correct, checks=checks, numbers=numbers, peak_bytes=peak)
    if calibrate == "control":
        rec.control = {"max_gap": max(control) if control else None}
    return rec
