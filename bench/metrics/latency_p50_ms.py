"""Median time from due (open loop) or submit (closed loop) to answer, over
every answered request of the window."""
import numpy as np


def read(run):
    lat = [r.latency_s for r in run.answered]
    return float(np.percentile(lat, 50)) * 1e3 if lat else None
