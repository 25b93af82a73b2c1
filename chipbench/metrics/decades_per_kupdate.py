"""Residual decades gained per 1,000 applied block updates: the solver's
progress per unit of work, which staleness and acceleration move."""


def read(w):
    return 1000.0 * w.decades / w.updates if w.updates else None
