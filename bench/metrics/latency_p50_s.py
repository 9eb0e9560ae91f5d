"""latency_p50_s: median latency of the requests offered in the window,
each timed from when it was due to when its sample was on the host."""
import numpy as np


def read(run):
    if not run.latencies_s:
        return None
    return float(np.percentile(run.latencies_s, 50))
