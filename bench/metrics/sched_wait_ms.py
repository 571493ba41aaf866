"""Mean queue wait, in ms, of the requests the scheduler executed inside
the window, on the scheduler's own clock: the growth of its ``wait_s``
counter (take time - submit time, summed) over the growth of
``executed``.  None where the program keeps no ``wait_s``."""


def read(record):
    before, after = record.timeline.counters
    if "wait_s" not in after:
        return None
    executed = after["executed"] - before["executed"]
    if executed <= 0:
        return None
    return (after["wait_s"] - before["wait_s"]) / executed * 1e3
