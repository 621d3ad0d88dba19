"""Causal attention's share of the chip's peak in the prefill: the
attention operations the traced window's prefills need
(bench/counts/dense_sublayers.py) over the device seconds charged to the
``attn_core`` scope inside the benchmark's ``prefill`` spans
(bench/scopes.py), times peak bf16 FLOP/s."""

from bench import scopes
from bench.counts import dense_sublayers as counts


def read(rec):
    if rec.trace is None or not rec.batches:
        return None
    busy = scopes.seconds(rec, "prefill", ("attn_core",))
    flops = len(rec.batches) * counts.prefill_attention_flops(
        rec.config, rec.batch, rec.prompt)
    return 100.0 * flops / busy / rec.peaks["bf16_flops_per_s"] if busy else None
