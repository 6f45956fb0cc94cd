"""Device-fault injection into the virtual chip's conductance stacks.

Layers `runtime.faults.MemristorFaults` (deterministic stuck-on/stuck-off
masks + per-core variation) onto a `Placement`: every main-grid core stack
gets its own seeded fault pattern (salted by stage index and by which side
of the differential pair it is), so the same chip always breaks the same
devices.  Aggregation cores are left ideal — they carry routing-sum unit
conductances, and the mapper treats them as part of the interconnect
fabric rather than programmable weight storage.

Faulted conductances flow everywhere the stacks flow: inference, the
backward error transport (a stuck device corrupts gradients through the
same cells, exactly as in the physical chip), and the pulse updates (which
cannot heal a stuck device — the injected mask is re-applied after every
`reapply` so training works around, not through, broken cells).
"""
from __future__ import annotations

import dataclasses

from repro.runtime.faults import MemristorFaults
from repro.sim.placer import Placement


def _stage_salts(index: int) -> tuple[int, int]:
    return 2 * index, 2 * index + 1


def _overlay(placement: Placement, faults: MemristorFaults, w_max: float,
             variation: bool) -> Placement:
    stages = []
    for st in placement.stages:
        sp, sm = _stage_salts(st.index)
        stages.append(dataclasses.replace(
            st,
            g_plus=faults.apply(st.g_plus, salt=sp, w_max=w_max,
                                variation=variation),
            g_minus=faults.apply(st.g_minus, salt=sm, w_max=w_max,
                                 variation=variation)))
    # a conductance write: the new version invalidates any envelope built
    # from the old placement
    return dataclasses.replace(placement, stages=stages,
                               version=placement.version + 1)


def inject_faults(placement: Placement, faults: MemristorFaults,
                  w_max: float = 1.0) -> Placement:
    """Return a placement whose main-grid stacks carry the fault overlay:
    per-core fabrication variation (applied once, here) plus the stuck
    masks."""
    if faults.is_null:
        return placement
    return _overlay(placement, faults, w_max, variation=True)


def reapply(placement: Placement, faults: MemristorFaults,
            w_max: float = 1.0) -> Placement:
    """Re-assert the stuck masks after training wrote new conductances
    (pulse updates cannot move a stuck device).  Same masks as
    `inject_faults` — pure function of (seed, stage, shape) — but without
    re-scaling by the fabrication variation, so the call is idempotent.
    `VirtualChip.train_step` does this automatically for chips built with
    faults."""
    if faults.is_null:
        return placement
    return _overlay(placement, faults, w_max, variation=False)
