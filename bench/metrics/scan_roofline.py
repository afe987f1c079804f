"""Share of the device's HBM roofline that the scans of the traced drains
reach: the least time their work needs over the device busy time during
them.

The least bytes of a drain: for each distinct column its queries read,
live rows x ceil(log2(distinct values)) bits, plus one output bitmap of
live rows / 8 bytes per query.  No exact encoding, shared read or kernel
reads less, and the data is unclustered, so skipping blocks cannot beat it
either; the share cannot pass 100%.  Only drains that lie wholly inside
the trace count.
"""
import math


def read(run):
    t = run.trace
    if t is None or not run.drains:
        return None
    bw = float(run.peaks["hbm_bytes_per_s"])
    bits = {}
    least = busy = 0.0
    for d in run.drains:
        if d.rows <= 0:
            continue
        nbytes = d.queries * d.rows / 8
        for c in d.columns:
            if c not in bits:
                bits[c] = max(1, math.ceil(math.log2(max(run.distinct(c), 2))))
            nbytes += d.rows * bits[c] / 8
        least += nbytes / bw
        busy += t.busy_s(d.t0, d.t1)
    if busy <= 0:
        return None
    return 100.0 * least / busy
