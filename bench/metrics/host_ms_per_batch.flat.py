"""Host time per drain, in ms: the mean over the traced window's
``bench.drain`` spans of the span's length less the device busy time
inside it (stacking, padding, dispatch, waiting and slicing on the host)."""


def read(record):
    r = record.trace
    if r is None or not r.drains:
        return None
    return sum((e - s) - busy for s, e, busy in r.drains) / len(r.drains) * 1e-6
