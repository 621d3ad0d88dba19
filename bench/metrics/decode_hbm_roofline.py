"""The decode step's share of its roofline: the least time the window's
decode steps could take on this chip, each the larger of its needed
operations over peak FLOP/s and its needed bytes over peak HBM bandwidth
(bench/counts: every weight read once, each stream's live keys and values
read once, the new ones written once; never the slots beyond the live
length), over the device-busy time inside the benchmark's ``decode``
spans (from the call to its tokens on the host) in the trace."""

from bench.counts import dense_decoder as counts


def read(rec):
    if rec.trace is None:
        return None
    busy = rec.trace.busy_in("decode")
    if busy <= 0:
        return None
    least = 0.0
    for b in rec.batches:
        for k in range(1, len(b["times"])):
            live = rec.prompt + k
            least += max(
                counts.decode_flops(rec.config, rec.batch, live)
                / rec.peaks["bf16_flops_per_s"],
                counts.decode_bytes(rec.config, rec.batch, live)
                / rec.peaks["hbm_bytes_per_s"])
    return 100.0 * least / busy if least else None
