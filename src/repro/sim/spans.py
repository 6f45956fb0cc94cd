"""Host spans of the simulator's hot path, for the profiler's trace.

Every public entry point of the virtual chip, the pipeline and the farm
opens an *entry span* named ``repro.<object>.<method>``
(``repro.chip.train_step``, ``repro.pipeline.serve``, ...).  Inside it the
host work falls into five *phase spans*, none of which holds another:

  * ``repro.prepare``: operands built before a compiled program (batch
    shapes, padded stacks, index maps, request queues, stacked requests);
  * ``repro.dispatch``: the call into a compiled program of
    `repro.sim.compiled`;
  * ``repro.writeback``: results handed back to the object's own layout
    (stacks scattered into the placement, padded outputs sliced, answers
    split and completed);
  * ``repro.sync``: a blocking device-to-host read (the counters);
  * ``repro.account``: the host's replay of counters (phase slots, NoC,
    links, IO).

Spans are host-side only: never inside a jitted function or a scan body,
and never one per request or per stage.  With the profiler off a span
costs a fraction of a microsecond (`jax.profiler.TraceAnnotation`), so
they are always on.  The eager reference paths (no compiled executor, or
faults) leave their per-stage work outside any phase span.
"""
from __future__ import annotations

import functools

from jax.profiler import TraceAnnotation, annotate_function

PREFIX = "repro."
PREPARE = "repro.prepare"
DISPATCH = "repro.dispatch"
WRITEBACK = "repro.writeback"
SYNC = "repro.sync"
ACCOUNT = "repro.account"
PHASES = (PREPARE, DISPATCH, WRITEBACK, SYNC, ACCOUNT)


def entry(name: str):
    """Decorator: the method runs inside the entry span ``repro.<name>``
    (``name`` is ``<object>.<method>``)."""
    return functools.partial(annotate_function, name=PREFIX + name)


def phase(name: str) -> TraceAnnotation:
    """A phase span, one of `PHASES`, as a context manager."""
    return TraceAnnotation(name)
