"""Seconds from the start of the process to the start of the window: data
from the seed, the program's problem, and a warm-up solve that compiles or
loads every program the window drives."""


def read(w):
    return w.setup_s
