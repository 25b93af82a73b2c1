"""Percent of the traced window in which no operation ran on the device."""


def read(w):
    return 100.0 * w.trace.idle_share if w.trace is not None else None
