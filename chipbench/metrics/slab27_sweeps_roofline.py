"""Percent of the least time (peaks.py) that HPCG's 27-point z-slab block
update (``problems/hpcg.py::_slab27_sweeps``) took on the device."""


def read(w):
    return w.roofline("_slab27_sweeps")
