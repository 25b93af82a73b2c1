"""Median of the telemetry ``block_eval`` spans (one worker's evaluation
of its block: the device plane's refresh and step, or the host block
update), in milliseconds."""

import numpy as np


def read(w):
    d = [e["t1"] - e["t0"] for e in w.events("block_eval")]
    return 1000.0 * float(np.median(d)) if d else None
