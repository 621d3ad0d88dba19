"""Driver for training cells: the program's own training loop on one chip.

Set-up builds one object, the launcher's jitted train step
(``repro.train.build_train_step`` with the state donated, AdamW from
``repro.optim``) with its state made from the benchmark's seeded weights
(made by the reference of the configuration's family,
``bench/family.py``), and drives it through ``repro.train.loop.run`` for
its first three steps on the benchmark's token stream fed through the
program's ``PrefetchIterator``.  Those steps compile and warm the step,
and are the steps the reference follows.  The same object then runs the window:
``run`` again, for as many steps as fill ``--seconds`` at the warm step
time, with no checkpoint and a silent logger.  Its per-step sync (block on
the loss, fetch every metric) is part of the loop users run, so it is
inside the window.

Traffic parameters (``bench/traffic/<mix>.json``): ``batch``, ``seq``,
and ``optimizer``: ``lr`` (held constant), ``b1``, ``b2``, ``eps``,
``weight_decay``, ``decay`` (the leaves weight decay applies to, with
``decay_source``), ``max_grad_norm``, ``z_loss``.  The cell's file
(``bench/workloads/<cell>.json``) holds the ``limits`` of the comparison.
"""

from __future__ import annotations

import gc
import inspect
import statistics
import time
from types import SimpleNamespace

import jax

from bench import common, compare, family, generator

CHECK_STEPS = 3
STACKED_NORMS = ("attn_norm", "mlp_norm")      # (layers, d) in the program
SPANS = ("step", "data_fetch")


class _Spanned:
    """The step and the data iterator with a host span around each call."""

    def __init__(self, step, it):
        self.step, self.it = step, it

    def __call__(self, state, batch):
        with jax.profiler.TraceAnnotation("step"):
            return self.step(state, batch)

    def __iter__(self):
        return self

    def __next__(self):
        with jax.profiler.TraceAnnotation("data_fetch"):
            return next(self.it)


def _hyper(opt: dict):
    return (opt["lr"], opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
            opt["max_grad_norm"], opt["z_loss"])


def _first_grad(prog, m, b1):
    """The first clipped gradient from AdamW's first moment after one
    step, m_1 / (1 - b1), on the host in the reference's layout (``prog``
    is the family's program mapping)."""
    return jax.tree_util.tree_map(lambda x: x / (1.0 - b1),
                                  prog.from_program(jax.device_get(m)))


def run(cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, t0: float, calibrate: str = ""):
    import repro.train as train_lib
    from repro.data import PrefetchIterator
    from repro.optim.optimizers import AdamW
    from repro.train.loop import LoopConfig
    from repro.train.loop import run as train_run
    from repro.train.step import cross_entropy

    ref, prog = family.load(config)
    cfg = prog.program_config(config)
    spec = ref.Spec.from_config(config)
    t, opt_p = traffic, traffic["optimizer"]
    z_default = inspect.signature(cross_entropy).parameters["z_loss"].default
    if z_default != opt_p["z_loss"]:
        raise ValueError(f"the program's z-loss is {z_default}, the cell "
                         f"states {opt_p['z_loss']}")
    device = jax.devices()[0]
    key = generator.weights_key(seed)
    # The key is an argument, not a constant: one program serves every seed.
    init = jax.jit(lambda k: ref.init_weights(spec, k))
    make = lambda: init(key)
    opt = AdamW(lr=lambda count: opt_p["lr"], b1=opt_p["b1"], b2=opt_p["b2"],
                eps=opt_p["eps"], weight_decay=opt_p["weight_decay"])
    state = train_lib.init_train_state(cfg, prog.to_program(make()), opt)
    step = jax.jit(train_lib.build_train_step(
        cfg, None, opt, max_grad_norm=opt_p["max_grad_norm"]),
        donate_argnums=(0,))
    stream = generator.TokenStream(cfg.vocab_size, t["batch"], t["seq"], seed)
    it = PrefetchIterator(stream, start_step=0)
    silent = lambda *a, **k: None

    def loop(state, fn, data, steps, history=None):
        lc = LoopConfig(total_steps=steps, ckpt_dir=None, log_every=1 << 30)
        return train_run(state, fn, data, lc, logger=silent, history=history)

    try:
        state, hist = loop(state, step, it, 1)
        grad = _first_grad(prog, state["opt"]["m"], opt_p["b1"])
        state, hist = loop(state, step, it, CHECK_STEPS, hist)
        program = {"losses": [h["loss"] for h in hist], "grad": grad,
                   "params": prog.from_program(jax.device_get(state["params"]))}
        warm = statistics.median(h["sec"] for h in hist[1:])
        n_steps = max(1, round(seconds / warm))
        setup_s = time.perf_counter() - t0

        out = {}
        if calibrate:
            window_s, window = 0.0, []
        else:
            fn, data = (_Spanned(step, it),) * 2 if trace else (step, it)
            with common.profiled(trace, SPANS, out):
                w_start = time.perf_counter()
                state, window = loop(state, fn, data, CHECK_STEPS + n_steps)
                window_s = time.perf_counter() - w_start
    finally:
        it.close()
    peak = common.peak_bytes(device)
    del state, step
    gc.collect()

    batches = [stream.batch(i) for i in range(CHECK_STEPS)]
    start = jax.device_get(make())

    def follow(batches, mode="fp32", decayed=opt_p["decay"]):
        return ref.follow_training(spec, _hyper(opt_p), decayed, make,
                                   batches, mode)

    reference = follow(batches)
    leaves = compare.training_leaves(program, reference, start)
    numbers = compare.training_numbers(program, reference, start, leaves)
    correct, checks = common.judge(numbers, cell["limits"])
    rec = SimpleNamespace(
        config=config, cell=cell, device=device,
        setup_s=setup_s, window_s=window_s, trace=out.get("trace"),
        steps=len(window), tokens=len(window) * t["batch"] * t["seq"],
        seq=t["seq"],
        attempted=len(window), failed=sum(int(h.get("skipped", 0)) for h in window),
        correct=correct, checks=checks, numbers=numbers, peak_bytes=peak)
    if calibrate:
        rec.details = {"losses": program["losses"],
                       "ref_losses": reference["losses"],
                       "worst": compare.worst(leaves),
                       "norm_leaves": _own_norm_change(program, reference,
                                                       start)}
    if calibrate == "control":
        def numbers_of(readings):
            return compare.training_numbers(readings, reference, start)
        rec.control = numbers_of(follow(batches, mode="fp8"))
        half = [{k: v[: t["batch"] // 2] for k, v in b.items()} for b in batches]
        rec.half_batch = numbers_of(follow(half))
        # The witness for the program's decay of the stacked norm scales:
        # the reference with that decay added, against the program.
        quirk = follow(batches, decayed=(*opt_p["decay"], *STACKED_NORMS))
        rec.details["norm_leaves_vs_quirk"] = _own_norm_change(
            program, quirk, start)
    return rec


def _norm_scales(tree):
    return {"layers": {k: tree["layers"][k] for k in STACKED_NORMS},
            "final_norm": tree["final_norm"]}


def _own_norm_change(prog, reference, start):
    """For each norm-scale leaf, the norm of the difference of the two
    parameter changes over the reference change's own norm (no median
    floor), so that a decay rule that departs on these leaves shows."""
    p, r, s0 = (_norm_scales(x) for x in
                (prog["params"], reference["params"], start))
    diff, own = compare.leaf_norms(p, r), compare.leaf_norms(r, s0)
    return {k: (diff[k] / own[k]).tolist() for k in diff}
