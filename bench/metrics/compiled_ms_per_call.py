"""Device time of the simulator's compiled programs (`sim/compiled.py`:
``chip_train``, ``chip_forward``, ``chip_backward``, ``chip_infer``,
``serve_scan``) per call, matched by their jit names, averaged over the
devices."""
from __future__ import annotations

PROGRAMS = r"(chip_train|chip_forward|chip_backward|chip_infer|serve_scan)"


def read(r):
    from bench import trace as tr
    if not r.red.devices or not r.calls:
        return None
    ns = [sum(e.dur for e in tr.matching(d.modules, PROGRAMS))
          for d in r.red.devices]
    if not any(ns):
        return None
    return sum(ns) / len(ns) * 1e-6 / r.calls
