"""Decode attention's share of its roofline: the keys and values the
traced window's decode steps need to read (each stream's live positions
already cached, never the slots beyond; bench/counts/dense_sublayers.py)
over peak HBM bandwidth, over the device seconds charged to the
``attn_core`` scope inside the benchmark's ``decode`` spans
(bench/scopes.py).  Bandwidth bounds it: a cached byte carries as many
operations as there are query heads per key/value head (16 for glm4-9b),
below the chip's ~240 per byte."""

from bench import scopes
from bench.counts import dense_sublayers as counts


def read(rec):
    if rec.trace is None:
        return None
    busy = scopes.seconds(rec, "decode", ("attn_core",))
    read_bytes = sum(
        counts.decode_kv_read_bytes(rec.config, rec.batch, rec.prompt + k)
        for b in rec.batches for k in range(1, len(b["times"])))
    return (100.0 * read_bytes / rec.peaks["hbm_bytes_per_s"] / busy
            if busy and read_bytes else None)
