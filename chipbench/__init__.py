"""Chip benchmark of the asynchronous fixed-point engine (see run.py)."""
