"""Serving driver: load a checkpoint (or fresh init) and serve batched
generation requests.

    PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --reduced \
        --ckpt-dir /tmp/ckpt --prompt-len 16 --steps 32 --batch 4

``--layers N`` cuts the depth as in ``repro.launch.train``; without
``--reduced`` the widths are the published ones.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import ckpt as ckpt_lib
from repro.configs import ARCH_IDS, get_config, reduced, with_layers
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serve import ServeEngine


def main(argv=None, params=None):
    """Serve with the command line ``argv`` (default ``sys.argv[1:]``).

    ``params`` serves an already-built parameter tree (for instance the
    one ``repro.launch.train.main`` returned) instead of initializing from
    ``--seed`` or restoring ``--ckpt-dir``.  Returns a dict: ``prompt``
    (B, P), generated ``tokens`` (B, steps), the engine's ``logits``
    (B, steps + 1, V; see ``ServeEngine.generate``), ``seconds`` of
    generation (compilation included) and the ``cfg`` served.
    """
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="glm4-9b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers (a multiple of "
                         "the arch's block period)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.layers is not None:
        cfg = with_layers(cfg, args.layers)

    if params is None:
        params = init_params(cfg, jax.random.PRNGKey(args.seed))
        if args.ckpt_dir and ckpt_lib.latest_step(args.ckpt_dir):
            state, step = ckpt_lib.restore(
                args.ckpt_dir, like={"params": params, "step": jnp.int32(0),
                                     "opt": None})
            print(f"restored params from step {step}")
            params = state["params"]

    engine = ServeEngine(cfg, params, None,
                         max_seq=args.prompt_len + args.steps + 8,
                         batch_size=args.batch)
    prompt = jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                                (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    t0 = time.perf_counter()
    out, logits = engine.generate(prompt, steps=args.steps,
                                  greedy=args.temperature == 0.0,
                                  key=jax.random.PRNGKey(args.seed + 2),
                                  temperature=max(args.temperature, 1e-3),
                                  return_logits=True)
    jax.block_until_ready(logits)
    dt = time.perf_counter() - t0
    tok = args.batch * args.steps
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({tok/dt:.1f} tok/s incl. compile)")
    for row in range(min(2, args.batch)):
        print(f" stream {row}:", list(map(int, out[row, :16])))
    return {"prompt": prompt, "tokens": out, "logits": logits,
            "seconds": dt, "cfg": cfg}


if __name__ == "__main__":
    main()
