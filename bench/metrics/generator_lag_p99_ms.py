"""99th percentile of send time minus due time over the window's requests,
from the harness's own clock: how late the load generator ran."""
import numpy as np


def read(run):
    if run.lag_s is None or not run.lag_s.size:
        return None
    return float(np.quantile(run.lag_s, 0.99, method="inverted_cdf")) * 1e3
