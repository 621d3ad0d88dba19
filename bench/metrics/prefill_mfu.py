"""The prefill's share of the chip's peak while it runs: the operations
the traced window's prefills need (bench/counts: every layer's products,
the causal half of attention, the head at the last position only) over
the device-busy seconds inside the benchmark's ``prefill`` spans (each
from the call to its first tokens on the host), times peak bf16 FLOP/s."""

from bench.counts import dense_decoder as counts


def read(rec):
    if rec.trace is None or not rec.batches:
        return None
    busy = rec.trace.busy_in("prefill")
    flops = len(rec.batches) * counts.prefill_flops(rec.config, rec.batch,
                                                    rec.prompt)
    return 100.0 * flops / busy / rec.peaks["bf16_flops_per_s"] if busy else None
