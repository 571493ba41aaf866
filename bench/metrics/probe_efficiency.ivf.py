"""Share, in percent, of the rows the IVF probe rounds gathered that were
list members and so were scored: the growth of the scheduler's
``rows_scored`` counter over that of ``rows_gathered`` in the window.  A
round gathers every list padded to the largest and keeps gathering for
queries that have stopped, so the rest is padding and frozen queries.
None where the program keeps no such counters."""


def read(record):
    before, after = record.timeline.counters
    if "rows_gathered" not in after:
        return None
    gathered = after["rows_gathered"] - before["rows_gathered"]
    if gathered <= 0:
        return None
    return 100.0 * (after["rows_scored"] - before["rows_scored"]) / gathered
