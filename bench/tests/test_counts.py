"""Operation and byte counts against hand counts and the program's own
parameter tree, and the peak table.  CPU only, shapes only."""

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench.counts import dense_decoder as counts
from bench.peaks import peaks

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name, expected", [
    ("phi4-mini-3.8b-2L", 815_938_560),
    ("glm4-9b-8L", 2_873_167_872),
])
def test_param_count_matches_program(name, expected):
    from bench import family
    from repro.models import init_params

    c = config(name)
    program_config = family.load(c)[1].program_config
    shapes = jax.eval_shape(lambda k: init_params(program_config(c), k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    program = sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes))
    assert counts.param_count(c) == program == expected


def test_train_flops_hand_count():
    c = config("phi4-mini-3.8b-2L")
    d, ff, V = 3072, 8192, 200064
    layer = d * 3072 + 2 * d * 1024 + 3072 * d + 3 * d * ff   # q, k+v, o, mlp
    n = 2 * layer + d * V                                     # head (tied)
    attn = 3 * 4 * 2 * 24 * 128 * (512 + 1) / 2              # causal, fwd+bwd
    assert counts.train_flops_per_token(c, 512) == pytest.approx(6 * n + attn)
    assert counts.train_flops_per_token(c, 512) == pytest.approx(4.9e9, rel=0.01)


def test_prefill_and_decode_hand_counts():
    c = config("glm4-9b-8L")
    d, ff, V, L = 4096, 13696, 151552, 8
    layer = d * 4096 + 2 * d * 256 + 4096 * d + 3 * d * ff
    P = 4096
    per_seq = 2 * L * layer * P + 4 * L * 4096 * P * (P + 1) // 2 + 2 * d * V
    assert counts.prefill_flops(c, 4, P) == 4 * per_seq
    assert counts.prefill_flops(c, 4, P) == pytest.approx(5.8e13, rel=0.01)

    live = 300
    assert counts.decode_flops(c, 32, live) == 32 * (
        2 * (L * layer + d * V) + 4 * L * 4096 * live)
    kv = 2 * L * 2 * 128 * 2                                  # bytes/position
    weights = (L * layer + d * V) * 2 + (2 * L + 1) * d * 4
    assert counts.decode_bytes(c, 32, live) == weights + 32 * live * kv
    assert weights == pytest.approx(4.5e9, rel=0.01)


def test_peaks_known_and_unknown_kind():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")
