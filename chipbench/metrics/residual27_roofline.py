"""Percent of the least time (peaks.py) that the coordinator's record of
HPCG's residual norm (``problems/hpcg.py::_residual_norm27``) took on the
device."""


def read(w):
    return w.roofline("_residual_norm27")
