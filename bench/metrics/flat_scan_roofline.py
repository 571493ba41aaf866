"""The flat scan's share of its roofline, in percent: for every drain in
the traced window, the least time the chip could take for the batch's
exact scan (the larger of 2*N*D*Q operations over peak FLOP/s and
N*D*4 + N*4 bytes, the float32 corpus and the price column read once, over
peak HBM bytes/s), summed, over the device's busy time in the window.  Q is
the bucket the batch ran in.  Counts the float32 corpus: a lane that reads
less needs this reader recounted first."""


def read(record):
    r = record.trace
    if r is None or not r.drains or r.busy_ns <= 0:
        return None
    cfg, peaks = record.cell.config, record.peaks
    n, d = cfg["rows"], cfg["dim"]
    sizes = [size for s, e, size in record.timeline.drains]
    if len(sizes) < len(r.drains):
        return None
    floor = 0.0
    for size in sizes[:len(r.drains)]:
        q = 1 << max(size - 1, 0).bit_length()
        floor += max(2.0 * n * d * q / peaks["flops_per_s"],
                     (n * d * 4 + n * 4) / peaks["hbm_bytes_per_s"])
    return 100.0 * floor / r.busy_s
