"""Roofline share of the forward kernel behind `ops.crossbar_fwd_stacked`:
the least time the chips could take for the window's forward work
(`work.fwd_work`: every row of a call through every real core once, the
conductances read once per call) over the kernel's device time."""
from __future__ import annotations


def read(r):
    from bench import trace as tr
    if not r.calls:
        return None
    ns = sum(tr.kernel_ns(d, "fwd") for d in r.red.devices)
    if not ns:
        return None
    m = r.samples / r.calls
    work = r.work.fwd_work(r.cfg, m) * r.calls
    least, _ = work.least_seconds(r.peak)
    return 100.0 * least * len(r.red.devices) / (ns * 1e-9) / r.chips
