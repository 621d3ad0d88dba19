"""The prefill's matrix products' share of the chip's peak: the
projection, FFN and last-position head operations the traced window's
prefills need (bench/counts/dense_sublayers.py) over the device seconds
charged to the ``attn_qkv``, ``attn_out``, ``ffn`` and ``head`` scopes
inside the benchmark's ``prefill`` spans (bench/scopes.py), times peak
bf16 FLOP/s."""

from bench import scopes
from bench.counts import dense_sublayers as counts

MATMULS = ("attn_qkv", "attn_out", "ffn", "head")


def read(rec):
    if rec.trace is None or not rec.batches:
        return None
    busy = scopes.seconds(rec, "prefill", MATMULS)
    flops = len(rec.batches) * counts.prefill_matmul_flops(
        rec.config, rec.batch, rec.prompt)
    return 100.0 * flops / busy / rec.peaks["bf16_flops_per_s"] if busy else None
