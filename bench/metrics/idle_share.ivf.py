"""Share of the traced window, in percent, in which no operation ran on
the device (the union of the XLA ops' intervals, averaged over the chips)."""


def read(record):
    r = record.trace
    if r is None or r.window_ns[1] <= r.window_ns[0]:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
