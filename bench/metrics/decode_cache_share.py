"""Share of the decode step's device time that moves the stacked cache:
the device seconds inside the benchmark's ``decode`` spans charged to
``attn_cache`` (the new key and value written into the cache), and to
the ``layers`` and ``unscoped`` operations whose result is the cache,
stacked or one layer's (the layer scan's slicing of the stacked cache and
its stacking of the new one, and the copies XLA puts in, which carry no
scope), over the device-busy seconds inside those spans
(bench/scopes.py).  The scan's slicing of the weights and XLA's other
operations are not cache movement and are left out."""

from bench import scopes

MOVES_CACHE = ("attn_cache", *scopes.moving(scopes.CACHE))


def read(rec):
    if rec.trace is None:
        return None
    moved = scopes.seconds(rec, "decode", MOVES_CACHE)
    busy = rec.trace.busy_in("decode")
    return 100.0 * moved / busy if moved and busy else None
