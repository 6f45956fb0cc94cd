"""Device program executions per call (train step or serving session):
the executions in the traced window on each device, averaged over the
devices, over the calls.  A host-bound path pays one dispatch for each."""
from __future__ import annotations


def read(r):
    if not r.red.devices or not r.calls:
        return None
    n = sum(len(d.modules) for d in r.red.devices) / len(r.red.devices)
    return n / r.calls
