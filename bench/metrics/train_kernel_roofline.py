"""Roofline share of the fused training kernel behind
`ops.crossbar_train_stacked`: the least time the chips could take for the
window's training work (`work.train_work`, counted on the placement's
real cores and the batch, never on the padded stacks the kernel is
handed) over the kernel's device time in the trace."""
from __future__ import annotations


def read(r):
    from bench import trace as tr
    if not r.calls:
        return None
    ns = sum(tr.kernel_ns(d, "train") for d in r.red.devices)
    if not ns:
        return None
    m = r.samples / r.calls
    work = r.work.train_work(r.cfg, m) * r.calls
    least, _ = work.least_seconds(r.peak)
    return 100.0 * least * len(r.red.devices) / (ns * 1e-9) / r.chips
