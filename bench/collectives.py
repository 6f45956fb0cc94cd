"""The cross-chip collectives of a reduced trace (`bench/trace.py`), and
the part of them that no other device work hides.

An ``XLA Ops`` event is named by its HLO instruction text, whose opcode
follows the result's shape: ``%psum.18 = f32[6,400,100]{...} all-reduce(
...)``.  The instruction's own name comes from the program (``psum``,
``pmax``), so a collective is told by its opcode: ``all-reduce``,
``all-gather``, ``reduce-scatter``, ``collective-permute``,
``all-to-all``, or the ``-start``/``-done`` pair of one run
asynchronously.  A pair counts from the start of its ``-start`` to the
end of its ``-done``: the transfer runs between them.
"""
from __future__ import annotations

import re

from bench import trace as tr

OPCODE = re.compile(r"\s(all-reduce|all-gather|reduce-scatter"
                    r"|collective-permute|all-to-all)(-start|-done)?\(")


def opcode(op_name: str) -> tuple[str, str] | None:
    """(collective, "" / "-start" / "-done") of an ``XLA Ops`` event, or
    None for any other operation."""
    m = OPCODE.search(op_name.partition(" = ")[2])
    return (m.group(1), m.group(2) or "") if m else None


def intervals(dev: tr.Device) -> list[tuple[int, int]]:
    """The device's collectives as (start, end): a synchronous one as its
    event, an asynchronous one from its ``-start`` to the next ``-done``
    of the same collective."""
    out, open_ = [], {}
    for o in sorted(dev.ops, key=lambda e: e.start):
        kind = opcode(o.name)
        if kind is None:
            continue
        name, phase = kind
        if phase == "-start":
            open_.setdefault(name, []).append(o.start)
        elif phase == "-done" and open_.get(name):
            out.append((open_[name].pop(0), o.end))
        else:
            out.append((o.start, o.end))
    return out


def others(dev: tr.Device) -> list[tuple[int, int]]:
    """The device's other work: every operation that is no collective and
    no control flow (a ``while`` spans the operations of its body)."""
    return [(o.start, o.end) for o in dev.ops
            if opcode(o.name) is None and not tr.CONTROL_FLOW.match(o.name)]


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def ms_per_call(r, exposed: bool) -> float | None:
    """Device milliseconds per call in collectives (the union of their
    intervals), or with ``exposed`` only the part in which no other
    operation runs on the device, averaged over the devices.  None
    without calls, or when no device ran a collective."""
    from bench import spans
    if not r.red.devices or not r.calls:
        return None
    per_dev = []
    for d in r.red.devices:
        coll = tr.union(intervals(d))
        ns = _length(coll)
        if exposed:
            ns -= spans.overlap_ns(coll, tr.union(others(d)))
        per_dev.append((ns, bool(coll)))
    if not any(found for _, found in per_dev):
        return None
    return sum(ns for ns, _ in per_dev) / len(per_dev) * 1e-6 / r.calls
