"""Device time of one lock-step IVF probe round, in ms: the device time
under the ``chase.ivf.probe_round`` scope in the traced window over the
growth of the scheduler's ``probe_rounds`` counter.  None where the trace
has no such scope or the program keeps no such counter."""
import harness

spans = harness.own("spans")


def read(record):
    r = spans.of(record)
    before, after = record.timeline.counters
    if r is None or "probe_rounds" not in after:
        return None
    rounds = after["probe_rounds"] - before["probe_rounds"]
    busy = r.scope_ns.get("chase.ivf.probe_round")
    if rounds <= 0 or not busy:
        return None
    return busy * 1e-6 / rounds
