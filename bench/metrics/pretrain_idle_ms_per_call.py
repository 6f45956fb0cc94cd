"""Device idle time inside the pipeline's pretraining calls (the entry
spans ``repro.pipeline.pretrain_step``): the device's idle gaps in the
traced window inside the union of those spans, averaged over the
devices, in milliseconds per call.  None for a program without those
spans (`bench/spans.py`)."""
from __future__ import annotations

SPAN = "repro.pipeline.pretrain_step"


def read(r):
    from bench import spans
    if not spans.named(r.red, SPAN):
        return None
    return spans.idle_ms_per_call(r, SPAN)
