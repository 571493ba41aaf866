"""Requests per executed batch over the window, from the scheduler's
counters (``executed`` / ``batches``)."""


def read(record):
    before, after = record.timeline.counters
    batches = after["batches"] - before["batches"]
    if batches <= 0:
        return None
    return (after["executed"] - before["executed"]) / batches
