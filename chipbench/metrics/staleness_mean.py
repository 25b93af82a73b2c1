"""Mean staleness of the applied updates, in updates applied between a
task's dispatch and its arrival (``RunResult.mean_staleness``, weighted
by each solve's applied updates)."""


def read(w):
    if not w.updates:
        return None
    return sum(r.mean_staleness * r.worker_updates
               for r in w.solves) / w.updates
