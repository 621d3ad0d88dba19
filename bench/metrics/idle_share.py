"""Share of the traced window in which no operation ran on the device:
1 - (union of device-operation intervals) / window.  One reader for every
cell: ``idle_share.<kind>`` names it per end-to-end metric it moves."""


def read(rec):
    if rec.trace is None:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s() / rec.trace.window_s())
