"""Percent of device-plane dispatches whose returned block the coordinator
wrote into its device-resident iterate, with no host copy
(``device_applies / device_dispatches``).  Nothing to read from a program
whose results lack the count."""


def read(w):
    dispatches = sum(r.device_dispatches for r in w.solves)
    if not dispatches or not all(hasattr(r, "device_applies")
                                 for r in w.solves):
        return None
    return 100.0 * sum(r.device_applies for r in w.solves) / dispatches
