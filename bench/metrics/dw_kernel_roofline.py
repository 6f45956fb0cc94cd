"""Roofline share of the local outer-product kernel behind
`ops.crossbar_dw_stacked`, which the farm runs unfused because its
pulses follow the cross-chip sum: the least time the chips could take for
the window's outer products (`work_farm.dw_work`, on the placement's real
cores and rows, never the padded envelope) over the kernel's device
time in the trace."""
from __future__ import annotations


def read(r):
    from bench import trace as tr
    from bench import work_farm
    if not r.calls:
        return None
    ns = sum(tr.kernel_ns(d, "dw") for d in r.red.devices)
    if not ns:
        return None
    m = r.samples / r.calls
    work = work_farm.dw_work(r.cfg, m, int(r.cfg["replicas"])) * r.calls
    least, _ = work.least_seconds(r.peak)
    return 100.0 * least * len(r.red.devices) / (ns * 1e-9) / r.chips
