"""95th percentile of latency over every request due in the window (host clock).

Latency runs from the request's due time to its completion. A request that
failed or never completed counts as infinitely late; where those are more
than 5% the percentile is infinite and the metric is left out (the run's
``missing_answers`` check fails it).
"""

import numpy as np


def read(run):
    if "latency_s" not in run.data:
        return None
    p95 = float(np.quantile(run.data["latency_s"], 0.95, method="inverted_cdf"))
    return p95 * 1e3 if np.isfinite(p95) else None
