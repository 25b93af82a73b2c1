"""Percent of the solves' wall time spent in residual records: the
telemetry ``record`` spans (``Coordinator.record``, the residual's
evaluation) over the solves' wall time.  Each solve counts the part of
its spans inside [0, wall_time]: the async loop's final record follows
the stop, past the wall time.  None where the spans carry no time (a
program whose records are instants)."""


def read(w):
    secs = wall = 0.0
    for r in w.solves:
        wall += r.wall_time
        if r.telemetry is None:
            continue
        for e in r.telemetry.events:
            if e["k"] == "record":
                secs += max(0.0, min(e["t1"], r.wall_time) - max(e["t0"], 0.0))
    return 100.0 * secs / wall if secs > 0 and wall > 0 else None
