"""The 95th percentile of request latency over every request due in the
window, from when it was due until its sliced result was on the host; a
request that failed counts as never answered."""
import numpy as np


def read(record):
    tl = record.timeline
    lat = np.where(tl.ok, tl.done - tl.due, np.inf)
    value = float(np.percentile(lat, 95) * 1e3)
    return value if np.isfinite(value) else None
