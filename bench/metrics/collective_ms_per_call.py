"""Device time of the cross-chip collectives per call: the union of the
intervals of the collective operations (`bench/collectives.py`: the
farm's reconciliation all-reduce and its error full-scale), averaged
over the devices, in milliseconds per call.  None where no device ran
one."""
from __future__ import annotations


def read(r):
    from bench import collectives
    return collectives.ms_per_call(r, exposed=False)
