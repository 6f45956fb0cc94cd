"""The part of the cross-chip collectives' device time in which no other
operation runs on the same device: the communication a step waits on,
averaged over the devices, in milliseconds per call
(`bench/collectives.py`).  None where no device ran a collective."""
from __future__ import annotations


def read(r):
    from bench import collectives
    return collectives.ms_per_call(r, exposed=True)
