"""Device idle time while the host builds operands before a compiled
program (``repro.prepare``: padded stacks, index maps, request queues,
stacked requests): the device's idle gaps in the traced window inside the
union of those spans, averaged over the devices, in milliseconds per
call.  None for a program without the spans (`bench/spans.py`)."""
from __future__ import annotations


def read(r):
    from bench import spans
    return spans.idle_ms_per_call(r, spans.PREPARE)
