"""The whole decode step's share of the chip's peak while it runs: the
operations the traced window's decode steps need (bench/counts; attention
over each stream's live positions only) over the device-busy seconds
inside the benchmark's ``decode`` spans (each from the call into
``ServeEngine.decode_step`` to its tokens on the host), times peak bf16
FLOP/s."""

from bench.counts import dense_decoder as counts


def read(rec):
    if rec.trace is None:
        return None
    busy = rec.trace.busy_in("decode")
    flops = sum(counts.decode_flops(rec.config, rec.batch, rec.prompt + k)
                for b in rec.batches for k in range(1, len(b["times"])))
    return 100.0 * flops / busy / rec.peaks["bf16_flops_per_s"] if busy and flops else None
