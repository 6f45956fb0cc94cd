"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals / window), as a percent,
averaged over the cell's devices."""
from __future__ import annotations


def read(r):
    from bench import trace as tr
    if not r.red.devices:
        return None
    busy = sum(tr.busy_ns(d) for d in r.red.devices) / len(r.red.devices)
    span = r.red.window[1] - r.red.window[0]
    return 100.0 * (1.0 - busy / span)
