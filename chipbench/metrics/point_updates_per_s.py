"""Millions of iterate components recomputed by applied block updates per
second of the window; each local sweep of an update counts."""


def read(w):
    return w.updates * w.cell.family.points_per_update(w.cell.config) \
        / w.seconds / 1e6
