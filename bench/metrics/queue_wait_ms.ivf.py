"""Median wait, in ms, from a request's due time until the start of the
drain that answered it, over the requests drained inside the window."""
import numpy as np


def read(record):
    tl = record.timeline
    inside = tl.ok & (tl.start <= tl.end)
    if not inside.any():
        return None
    return float(np.median(tl.start[inside] - tl.due[inside]) * 1e3)
