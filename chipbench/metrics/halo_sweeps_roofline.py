"""Percent of the least time (peaks.py) that the Jacobi halo-sweep block
update (``problems/jacobi.py::_halo_sweeps``) took on the device."""


def read(w):
    return w.roofline("_halo_sweeps")
