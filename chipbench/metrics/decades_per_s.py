"""Residual decades gained per second of the window, summed over its
solves (each from its first record to its last)."""


def read(w):
    return w.decades / w.seconds
