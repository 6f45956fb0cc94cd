"""Device idle time while the host calls into a compiled program
(``repro.dispatch``: argument handling and launch): the device's idle
gaps in the traced window inside the union of those spans, averaged over
the devices, in milliseconds per call.  None for a program without the
spans (`bench/spans.py`)."""
from __future__ import annotations


def read(r):
    from bench import spans
    return spans.idle_ms_per_call(r, spans.DISPATCH)
