"""Requests answered inside the window, per second of the window."""
import numpy as np


def read(record):
    tl = record.timeline
    return float(np.count_nonzero(tl.ok & (tl.done <= tl.end))
                 / (tl.end - tl.t0))
