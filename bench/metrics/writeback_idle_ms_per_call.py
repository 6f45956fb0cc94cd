"""Device idle time while the host hands results back to the simulator
object's layout (``repro.writeback``: stacks scattered into the
placement, padded outputs sliced, answers completed and stacked): the
device's idle gaps in the traced window inside the union of those spans,
averaged over the devices, in milliseconds per call.  None for a program
without the spans (`bench/spans.py`)."""
from __future__ import annotations


def read(r):
    from bench import spans
    return spans.idle_ms_per_call(r, spans.WRITEBACK)
