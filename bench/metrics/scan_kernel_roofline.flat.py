"""The fused flat scan kernel's share of its roofline, in percent: the
least time the chip could take for every traced drain's exact scan
(``flat_scan_roofline``'s floor: the larger of 2*N*D*Q operations over
peak FLOP/s and N*D*4 + N*4 bytes over peak HBM bytes/s, Q the drain's
bucket), over the device time under the ``chase.flat.scan`` scope.  None
where no such scope is in the trace."""
import harness

spans = harness.own("spans")


def read(record):
    r = spans.of(record)
    if r is None or not r.drains or not r.scope_ns.get("chase.flat.scan"):
        return None
    cfg, peaks = record.cell.config, record.peaks
    n, d = cfg["rows"], cfg["dim"]
    floor = sum(max(2.0 * n * d * dr.bucket / peaks["flops_per_s"],
                    (n * d * 4 + n * 4) / peaks["hbm_bytes_per_s"])
                for dr in r.drains)
    return 100.0 * floor / (r.scope_ns["chase.flat.scan"] * 1e-9)
