"""Operations and bytes of a dense decoder's sublayers, the parts that
``dense_decoder`` counts whole.  The same rules: needed work only, from
the shapes alone (the causal half of attention, the head at the last
position, no cache slot beyond the live length), so that the parts add
up to ``dense_decoder.prefill_flops`` and ``dense_decoder.decode_bytes``.
"""

from __future__ import annotations

from bench.counts.dense_decoder import (attention_flops,
                                        kv_bytes_per_position,
                                        layer_matmul_params, matmul_params)


def prefill_attention_flops(cfg: dict, batch: int, prompt: int) -> int:
    """Causal attention of a prefill: token i attends i + 1 positions."""
    return batch * attention_flops(cfg, 1) * prompt * (prompt + 1) // 2


def prefill_matmul_flops(cfg: dict, batch: int, prompt: int) -> int:
    """Every layer's products for every token, and the head at the last
    position only."""
    per_seq = (2 * cfg["num_hidden_layers"] * layer_matmul_params(cfg) * prompt
               + 2 * cfg["hidden_size"] * cfg["vocab_size"])
    return batch * per_seq


def decode_weight_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """The matrix weights one decode step reads once: every layer's and
    the head's (``matmul_params``)."""
    return matmul_params(cfg) * dtype_bytes


def decode_kv_read_bytes(cfg: dict, batch: int, live: int,
                         dtype_bytes: int = 2) -> int:
    """Keys and values a decode step reads: each stream's ``live - 1``
    positions already cached (the new position is written, not read)."""
    return batch * (live - 1) * kv_bytes_per_position(cfg, dtype_bytes)
