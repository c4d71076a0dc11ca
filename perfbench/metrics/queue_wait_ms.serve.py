"""p95 of each request's queue wait, latency minus service, in the traced part of the window.

Both are the engine's own per-request fields (``latency_s`` from the due
time, ``service_s`` from admission to a lane).
"""

import numpy as np


def read(run):
    d = run.data
    if "traced_s" not in d:
        return None
    keep = d["completed"] & (d["due_s"] < d["traced_s"])
    if not keep.any():
        return None
    wait = d["latency_s"][keep] - d["service_s"][keep]
    return float(np.quantile(wait, 0.95, method="inverted_cdf")) * 1e3
