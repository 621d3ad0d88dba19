"""The decode step's matrix products' share of their roofline: the
matrix weights every step reads once (bench/counts/dense_sublayers.py),
times the traced window's decode steps, over peak HBM bandwidth, over the
device seconds that read them inside the benchmark's ``decode`` spans
(bench/scopes.py): the ``attn_qkv``, ``attn_out``, ``ffn`` and ``head``
scopes, and the ``layers`` and ``unscoped`` operations whose result is a
matrix weight (the layer scan's slices of the attention weights, which
copy them out of HBM before their products run).  Bandwidth bounds it: at
a batch of 32 a weight byte carries 32 operations, below the chip's ~240
per byte."""

from bench import scopes
from bench.counts import dense_sublayers as counts

READS_WEIGHTS = ("attn_qkv", "attn_out", "ffn", "head",
                 *scopes.moving(scopes.WEIGHTS))


def read(rec):
    if rec.trace is None:
        return None
    busy = scopes.seconds(rec, "decode", READS_WEIGHTS)
    steps = sum(len(b["times"]) - 1 for b in rec.batches)
    weights = steps * counts.decode_weight_bytes(rec.config)
    return (100.0 * weights / rec.peaks["hbm_bytes_per_s"] / busy
            if busy and weights else None)
