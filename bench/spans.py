"""The program's own host spans in a reduced trace, and the device's idle
time inside each phase of its calls.

The simulator (`src/repro/sim/spans.py`) opens an *entry span*
``repro.<object>.<method>`` around each public call and, inside it, one
*phase span* around each piece of host work: ``repro.prepare`` (operands
built before a compiled program), ``repro.dispatch`` (the call into it),
``repro.writeback`` (results handed back to the object's layout),
``repro.sync`` (a blocking device-to-host read) and ``repro.account``
(the host's counter replay).  They run on the Python thread, so
`trace.reduce` keeps them in ``Reduced.host``, on the device trace's
clock.

Every reader built on this module returns None when the trace has no
device or no entry span in the window (a program without the spans), and
0.0 for a phase that has no span while the entry spans are there.
"""
from __future__ import annotations

from bench import trace as tr

PREFIX = "repro."
PREPARE = "repro.prepare"
DISPATCH = "repro.dispatch"
WRITEBACK = "repro.writeback"
SYNC = "repro.sync"
ACCOUNT = "repro.account"
PHASES = (PREPARE, DISPATCH, WRITEBACK, SYNC, ACCOUNT)


def entries(red: tr.Reduced) -> list[tr.Event]:
    """The program's entry spans that overlap the window."""
    return [e for e in red.host
            if e.name.startswith(PREFIX) and e.name not in PHASES]


def named(red: tr.Reduced, name: str) -> list[tr.Event]:
    """Host events called ``name`` that overlap the window."""
    return [e for e in red.host if e.name == name]


def overlap_ns(a, b) -> int:
    """Nanoseconds shared by two sorted lists of disjoint (start, end)
    intervals."""
    out = i = j = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_ns(red: tr.Reduced, intervals) -> float:
    """Device idle time of the window that falls inside ``intervals``
    (host time, any overlap), averaged over the devices."""
    inside = tr.union(intervals)
    return sum(overlap_ns(tr.gaps(d, red.window), inside)
               for d in red.devices) / len(red.devices)


def _readable(r) -> bool:
    return bool(r.red.devices) and bool(r.calls) and bool(entries(r.red))


def idle_ms_per_call(r, phase: str) -> float | None:
    """Device idle milliseconds inside the union of ``phase`` spans, per
    call of the window."""
    if not _readable(r):
        return None
    spans = [(e.start, e.end) for e in named(r.red, phase)]
    return idle_ns(r.red, spans) * 1e-6 / r.calls


def spans_per_call(r, phase: str) -> float | None:
    """``phase`` spans in the window, per call."""
    if not _readable(r):
        return None
    return len(named(r.red, phase)) / r.calls
