"""Mean of the IVF lists probed per request (the served results'
``stats["probes"]``) over the requests answered inside the window."""
import numpy as np


def read(record):
    tl = record.timeline
    inside = tl.ok & (tl.done <= tl.end)
    if not inside.any():
        return None
    return float(tl.answers["probes"][inside].mean())
