"""Operations and bytes a dense decoder needs, from its shapes alone.

Every count is of what the algorithm needs, not of what an implementation
happens to do: a multiply-add is 2 operations, attention counts only the
causal (needed) half of the score matrix, and recomputed work, padding
and cache slots beyond the live length do not count.  So a count stays
the same whatever implements the step, and a share of a peak built on it
cannot pass 100 % unless the time leaves out part of the work.

Notation (one configuration file, HF key names): d = hidden_size, H / KH
query and key/value heads of size hd, F = intermediate_size, V =
vocab_size, L = layers.
"""

from __future__ import annotations


def _sizes(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"], bool(cfg["tie_word_embeddings"]))


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer's matrix products: Wq (d x H hd), Wk and Wv
    (d x KH hd each), Wo (H hd x d) and the SwiGLU Wgate, Wup (d x F) and
    Wdown (F x d)."""
    d, H, KH, hd, F, _, _, _ = _sizes(cfg)
    return d * H * hd + 2 * d * KH * hd + H * hd * d + 3 * d * F


def param_count(cfg: dict) -> int:
    """Every parameter: the embedding (V x d), the head (d x V) unless
    tied, per layer its matrices and two norm scales (2 d), and the final
    norm scale (d)."""
    d, _, _, _, _, V, L, tied = _sizes(cfg)
    return V * d * (1 if tied else 2) + L * (layer_matmul_params(cfg) + 2 * d) + d


def matmul_params(cfg: dict) -> int:
    """Weights every token multiplies: all layers' matrices and the head
    (d x V, the embedding table when tied).  The embedding lookup reads
    one row and is no product."""
    d, _, _, _, _, V, L, _ = _sizes(cfg)
    return L * layer_matmul_params(cfg) + d * V


def attention_flops(cfg: dict, context: int) -> int:
    """Forward operations of one query token's attention over ``context``
    key positions, all layers: q k^T and p v are each 2 hd operations per
    head and position, so 4 H hd per position."""
    _, H, _, hd, _, _, L, _ = _sizes(cfg)
    return 4 * L * H * hd * context


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward operations per trained token, no recompute.

    The forward is 2 operations per matrix weight (``matmul_params``) plus
    the causal attention, whose mean context over a sequence of ``seq``
    positions is (seq + 1) / 2.  The backward takes twice the forward (one
    product for the input's gradient, one for the weight's).  So
    6 N + 3 * attention_flops(mean context)."""
    return 6 * matmul_params(cfg) + 3 * attention_flops(cfg, 1) * (seq + 1) / 2


def prefill_flops(cfg: dict, batch: int, prompt: int) -> int:
    """Operations a prefill of ``batch`` prompts of ``prompt`` tokens
    needs: every layer's products for every token, causal attention
    (token i attends i + 1 positions, sum = P (P + 1) / 2), and the head
    at the last position only, which is all that a first token needs."""
    d, _, _, _, _, V, L, _ = _sizes(cfg)
    per_seq = (2 * L * layer_matmul_params(cfg) * prompt
               + attention_flops(cfg, 1) * prompt * (prompt + 1) // 2
               + 2 * d * V)
    return batch * per_seq


def decode_flops(cfg: dict, batch: int, live: int) -> int:
    """Operations of one decode step for ``batch`` streams whose new token
    attends ``live`` positions (the prompt, the tokens so far and itself):
    2 per matrix weight and the attention over the live positions."""
    return batch * (2 * matmul_params(cfg) + attention_flops(cfg, live))


def kv_bytes_per_position(cfg: dict, dtype_bytes: int = 2) -> int:
    """Key and value of one position, all layers: 2 L KH hd elements."""
    _, _, KH, hd, _, _, L, _ = _sizes(cfg)
    return 2 * L * KH * hd * dtype_bytes


def decode_bytes(cfg: dict, batch: int, live: int, dtype_bytes: int = 2,
                 norm_bytes: int = 4) -> int:
    """Bytes one decode step needs to move: every weight it multiplies
    read once (``matmul_params`` in ``dtype_bytes``, the norm scales in
    ``norm_bytes``), each stream's key and value for the ``live - 1``
    positions already cached read once, and the new position's key and
    value written once.  Slots beyond the live length do not count."""
    d, _, _, _, _, _, L, _ = _sizes(cfg)
    weights = matmul_params(cfg) * dtype_bytes + (2 * L + 1) * d * norm_bytes
    return weights + batch * live * kv_bytes_per_position(cfg, dtype_bytes)
