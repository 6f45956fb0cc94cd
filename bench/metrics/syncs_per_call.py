"""Blocking device-to-host reads per call: the program's ``repro.sync``
spans (the counters' ``np.asarray``) in the traced window over its calls.
None for a program without the spans (`bench/spans.py`)."""
from __future__ import annotations


def read(r):
    from bench import spans
    return spans.spans_per_call(r, spans.SYNC)
