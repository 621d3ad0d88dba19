"""The program's side of the dense decoder family
(``bench/reference/dense_decoder.py``): its model configuration from a
configuration file, and the reference's weight tree in the program's
parameter layout (``bench/family.py`` has the contract)."""

from __future__ import annotations

import dataclasses

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
