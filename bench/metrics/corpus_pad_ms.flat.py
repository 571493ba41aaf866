"""Device time of the flat lane's padded corpus copy, in ms per drain: the
device time under the ``chase.flat.pad_corpus`` scope in the traced window
over its ``chase.drain`` spans.  0 where the other ``chase.flat.*`` scopes
ran and this one did not (no copy made); None where no ``chase.flat.*``
scope is in the trace."""
import harness

spans = harness.own("spans")


def read(record):
    r = spans.of(record)
    if r is None or not r.drains or not any(
            s.startswith("chase.flat.") for s in r.scope_ns):
        return None
    return r.scope_ns.get("chase.flat.pad_corpus", 0.0) / len(r.drains) * 1e-6
