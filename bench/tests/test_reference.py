"""The plain reference against the program (``repro.models``,
``repro.train``, ``repro.serve``) at a reduced size in float32 on the CPU:
the forward, prefill plus cached decode, and three AdamW training steps.

Both sides compute in float32 here, so they differ only by the order of
float32 sums (the program's blockwise online softmax against the
reference's direct one, fused against unfused products): about 1e-6 of
the logit scale.  The tolerances below leave a factor of ten or more over
what was seen, and stay far below any error of the mathematics (a wrong
rotary pair, head grouping or mask moves logits by their whole scale)."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import family, generator
from bench import compare as C
from bench.reference import dense_decoder as R

TINY = {"name": "tiny", "hidden_size": 64, "intermediate_size": 96,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_hidden_layers": 2, "vocab_size": 384, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-6, "torch_dtype": "float32",
        "reference": "bench/reference/dense_decoder.py"}
TIED = dict(TINY, arch="phi4-mini-3.8b", tie_word_embeddings=True)
UNTIED = dict(TINY, arch="glm4-9b", tie_word_embeddings=False)
ROOT = Path(__file__).resolve().parents[2]
# The program mapping, read as the harness reads it: through the family
# the configuration names.
P = family.load(TINY)[1]


def all_logits(spec, w, tokens):
    h = R.hidden(spec, "fp32", w, tokens)
    return R.mm("bsd,vd->bsv", h, R.head_table(spec, w), "fp32")


@pytest.mark.parametrize("config", [TIED, UNTIED], ids=["tied", "untied"])
def test_forward_matches_program(config):
    from repro.models import forward

    cfg, spec = P.program_config(config), R.Spec.from_config(config)
    w = R.init_weights(spec, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, spec.vocab)
    got = forward(P.to_program(w), {"tokens": tokens}, cfg, None,
                  mode="train")[0]
    want = all_logits(spec, w, tokens)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=0)


def test_prefill_and_cached_decode_match_reference():
    from repro.serve import ServeEngine

    cfg, spec = P.program_config(UNTIED), R.Spec.from_config(UNTIED)
    w = R.init_weights(spec, jax.random.PRNGKey(2))
    engine = ServeEngine(cfg, P.to_program(w), None, max_seq=48,
                         batch_size=3)
    prompt = jnp.asarray(generator.prompts(5, 0, 3, 20, spec.vocab))
    tokens, logits = engine.generate(prompt, steps=10, return_logits=True)
    seq = jnp.concatenate([prompt, tokens], axis=1)
    want = all_logits(spec, w, seq)[:, 19:]
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(logits, want, atol=2e-5 * scale, rtol=0)
    # the served-token gap the benchmark compares reads ~0 here
    gap, top = R.gaps_and_top(spec, "fp32", w, seq[:, :-1], tokens)
    assert float(gap.max()) <= 1e-4 * scale
    np.testing.assert_array_equal(top, tokens)


def test_three_training_steps_match_program():
    import repro.train as train_lib
    from repro.optim.optimizers import AdamW

    cfg, spec = P.program_config(TIED), R.Spec.from_config(TIED)
    hp = (1e-3, 0.9, 0.95, 1e-8, 0.1, 1.0, 1e-4)
    decay = json.loads((ROOT / "bench" / "traffic" / "train-b4s512.json")
                       .read_text())["optimizer"]["decay"]
    key = jax.random.PRNGKey(3)
    make = lambda: R.init_weights(spec, key)
    stream = generator.TokenStream(spec.vocab, 4, 32, seed=7)
    batches = [stream.batch(i) for i in range(3)]

    opt = AdamW(lr=lambda c: hp[0], b1=hp[1], b2=hp[2], eps=hp[3],
                weight_decay=hp[4])
    state = train_lib.init_train_state(cfg, P.to_program(make()), opt)
    step = jax.jit(train_lib.build_train_step(cfg, None, opt,
                                              max_grad_norm=hp[5]))
    losses = []
    for i, b in enumerate(batches):
        state, met = step(state, b)
        losses.append(float(met["loss"]))
        if i == 0:
            grad = jax.tree_util.tree_map(
                lambda x: x / 0.1,
                P.from_program(jax.device_get(state["opt"]["m"])))
    params = P.from_program(jax.device_get(state["params"]))
    start = jax.device_get(make())

    ref = R.follow_training(spec, hp, decay, make, batches)
    np.testing.assert_allclose(losses, ref["losses"], rtol=1e-5)
    ref_grad = C.leaf_norms(ref["grad"])
    gap = C.leaf_norms(grad, ref["grad"])
    for name in ref_grad:
        np.testing.assert_array_less(gap[name], 1e-4 * ref_grad[name],
                                     err_msg=name)
    # The program decays every leaf of rank 2 or more, and so the stacked
    # (layers, d) norm scales too, where the stated rule decays matrices
    # only (PERF.md, Open questions).  Those two leaves may follow either
    # rule, so that the program's fix keeps this test; every other leaf
    # follows the stated one.
    quirk = R.follow_training(spec, hp, (*decay, "attn_norm", "mlp_norm"),
                              make, batches)
    change = C.leaf_norms(params, start)
    for name, want in C.leaf_norms(ref["params"], start).items():
        alike = [want] + ([C.leaf_norms(quirk["params"], start)[name]]
                          if name in ("attn_norm", "mlp_norm") else [])
        assert any(np.allclose(change[name], w, rtol=1e-3) for w in alike), (
            name, change[name], alike)
