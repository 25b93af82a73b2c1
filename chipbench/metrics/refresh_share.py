"""Percent of device-plane dispatches that re-shipped their resident block
from the host iterate (``device_refreshes / device_dispatches``)."""


def read(w):
    dispatches = sum(r.device_dispatches for r in w.solves)
    if not dispatches:
        return None
    return 100.0 * sum(r.device_refreshes for r in w.solves) / dispatches
