"""95th percentile of the telemetry ``task`` spans (dispatch to arrival of
one worker task), in milliseconds."""

import numpy as np


def read(w):
    d = [e["t1"] - e["t0"] for e in w.events("task")]
    return 1000.0 * float(np.percentile(d, 95)) if d else None
