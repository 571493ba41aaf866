"""Host time after the device, in ms per drain: the mean over the traced
window's ``chase.drain`` spans of their ``chase.fetch`` time less the
device busy time inside it (the host waiting on an idle device, and the
copy back), plus their ``chase.slice`` time (per-request results).  None
where the program writes no ``chase.*`` spans."""
import harness

spans = harness.own("spans")


def read(record):
    r = spans.of(record)
    if r is None or not r.drains:
        return None
    return sum(d.parts.get("chase.fetch", 0.0) - d.fetch_busy_ns
               + d.parts.get("chase.slice", 0.0)
               for d in r.drains) / len(r.drains) * 1e-6
