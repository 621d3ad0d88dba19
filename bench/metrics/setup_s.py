"""Seconds from process start to the start of the window: loading,
weights, warm-up and, in a run that compiles, compilation."""


def read(rec):
    return rec.setup_s
