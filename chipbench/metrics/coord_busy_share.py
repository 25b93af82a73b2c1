"""Percent of the solves' wall time the coordinator spent on its own work
(apply, inline fires and records), as the thread executor meters it
(``RunResult.coordinator_busy_frac``, weighted by each solve's wall)."""


def read(w):
    wall = sum(r.wall_time for r in w.solves)
    busy = sum(r.coordinator_busy_frac * r.wall_time for r in w.solves)
    return 100.0 * busy / wall if busy > 0 else None
