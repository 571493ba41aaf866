"""Host time before the device, in ms per drain: the mean over the traced
window's ``chase.drain`` spans of their ``chase.stack`` + ``chase.pad`` +
``chase.dispatch`` children (stacking the binds, padding them to the
bucket, dispatching the executable).  None where the program writes no
``chase.*`` spans."""
import harness

spans = harness.own("spans")
PARTS = ("chase.stack", "chase.pad", "chase.dispatch")


def read(record):
    r = spans.of(record)
    if r is None or not r.drains:
        return None
    return sum(sum(d.parts.get(p, 0.0) for p in PARTS)
               for d in r.drains) / len(r.drains) * 1e-6
