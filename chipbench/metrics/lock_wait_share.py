"""Percent of the workers' time spent waiting for the coordinator lock:
the telemetry ``lock_wait`` spans (a thread worker reaching the lock, at
dispatch and at arrival, until it holds it) over the workers times the
solves' wall time."""


def read(w):
    waits = w.events("lock_wait")
    wall = sum(r.wall_time for r in w.solves)
    if not waits or wall <= 0:
        return None
    secs = sum(e["t1"] - e["t0"] for e in waits)
    return 100.0 * secs / (w.cell.config["n_workers"] * wall)
