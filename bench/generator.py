"""The benchmark's own traffic: everything a cell feeds the program is
made here from ``--seed`` and the cell's traffic parameters, so no change
to the program can move it.

- ``TokenStream`` is a copy of the program's synthetic training stream
  (``repro.data.pipeline.SyntheticLM``): row r of batch i is a pure
  function of (seed, i, r), every row differs, and a batch is
  ``{"tokens", "labels"}`` with labels the tokens shifted by one.
- ``prompts`` draws one lockstep batch of prompts, uniform over the
  vocabulary, as a pure function of (seed, batch index).
"""

from __future__ import annotations

import numpy as np

SEED_RANGE = 2**63


def weights_key(seed: int):
    """The JAX key the weights are made from: a 32-bit hash of ``seed``,
    so any whole number (beyond 32 bits too) gives a valid key."""
    import jax
    state = np.random.SeedSequence(seed % SEED_RANGE).generate_state(1)
    return jax.random.PRNGKey(int(state[0]))


class TokenStream:
    """tokens[t + 1] = (tokens[t] + drift) % vocab, with 2 % of positions
    replaced by uniform noise.  ``batch(i)`` is pure in (seed, i)."""

    def __init__(self, vocab: int, batch: int, seq: int, seed: int):
        self.vocab, self.rows, self.seq = vocab, batch, seq
        self.seed = seed % SEED_RANGE

    def batch(self, step: int) -> dict:
        rows = []
        base = step * self.rows
        drift = 1 + self.seed % max(self.vocab - 1, 1)
        for r in range(self.rows):
            rng = np.random.default_rng((self.seed, base + r))
            start = rng.integers(0, self.vocab)
            seq = (start + drift * np.arange(self.seq + 1)) % self.vocab
            noise = rng.random(self.seq + 1) < 0.02
            seq = np.where(noise, rng.integers(0, self.vocab, self.seq + 1), seq)
            rows.append(seq)
        tok = np.stack(rows).astype(np.int32)
        return {"tokens": tok[:, :-1], "labels": tok[:, 1:]}


def prompts(seed: int, index: int, batch: int, length: int, vocab: int):
    """Lockstep batch ``index``: (batch, length) int32 token ids."""
    rng = np.random.default_rng((seed % SEED_RANGE, index))
    return rng.integers(0, vocab, (batch, length), dtype=np.int32)
