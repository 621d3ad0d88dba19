"""What every driver shares: the program's model configuration from a
configuration file, the weight tree in the program's layout, host spans,
the profiler around the window, and the comparison that decides
``correct``."""

from __future__ import annotations

import contextlib
import dataclasses
import math
import shutil
import tempfile

import jax

from bench import trace as trace_lib

# configuration-file key -> the program's ModelConfig field
CONFIG_FIELDS = {
    "hidden_size": "d_model", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "num_hidden_layers": "num_layers", "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta", "torch_dtype": "dtype",
}


def program_config(config: dict):
    """The program's ModelConfig for ``config['arch']`` with every size the
    configuration file states; the file is the configuration as run."""
    from repro.configs import get_config

    cfg = get_config(config["arch"])
    cfg = dataclasses.replace(
        cfg, **{f: config[k] for k, f in CONFIG_FIELDS.items()})
    if cfg.family != "dense" or cfg.mlp_type != "swiglu":
        raise ValueError(f"{config['name']}: the dense-decoder reference "
                         f"covers dense SwiGLU models, not {cfg.family}")
    return cfg


def to_program(w: dict) -> dict:
    """The reference weight tree in the program's parameter layout (the
    same arrays; nothing is copied)."""
    lw = w["layers"]
    params = {"embed": w["embed"], "norm_final": w["final_norm"],
              "blocks": {"pos0": {
                  "norm_mixer": lw["attn_norm"], "norm_ffn": lw["mlp_norm"],
                  "attn": {k: lw[k] for k in ("wq", "wk", "wv", "wo")},
                  "mlp": {k: lw[k] for k in ("w_gate", "w_up", "w_down")}}}}
    if "head" in w:
        params["lm_head"] = w["head"]
    return params


def from_program(params: dict) -> dict:
    """Inverse of ``to_program``."""
    b = params["blocks"]["pos0"]
    w = {"embed": params["embed"], "final_norm": params["norm_final"],
         "layers": {"attn_norm": b["norm_mixer"], "mlp_norm": b["norm_ffn"],
                    **b["attn"], **b["mlp"]}}
    if "lm_head" in params:
        w["head"] = params["lm_head"]
    return w


def span(on: bool, name: str):
    """A profiler span around one call into the program, when tracing."""
    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


@contextlib.contextmanager
def profiled(on: bool, span_names, out: dict):
    """Trace the body when ``on``; the reduced trace lands in
    ``out['trace']``.  The raw trace is written under TMPDIR and removed
    once read."""
    if not on:
        yield
        return
    logdir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(logdir)
        try:
            with jax.profiler.TraceAnnotation(trace_lib.WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()
        out["trace"] = trace_lib.read(trace_lib.newest_xplane(logdir),
                                      span_names)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def peak_bytes(device):
    stats = device.memory_stats()
    return None if stats is None else int(stats.get("peak_bytes_in_use", 0))


def judge(numbers: dict, limits: dict):
    """``correct`` and the checks: each number with its limit.  A number
    that is missing or not finite fails."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and math.isfinite(value) and value <= limit
        ok = ok and good
        checks[name] = {"value": value, "limit": limit}
    return ok, checks
