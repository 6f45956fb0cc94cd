"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read.

A trace holds one plane per device (``/device:TPU:<n>``) and one for the
host (``/host:CPU``).  On a device plane the line ``XLA Modules`` has one
event per program execution, named ``jit_<function>(<id>)``, and the line
``XLA Ops`` one per operation inside it, named by its HLO instruction text
(``%fusion.66 = f32[2400,64]{...} fusion(...)``); control flow nests, so a
``while`` event spans the operations of its body.  A Pallas kernel is a
``custom-call`` to ``tpu_custom_call`` and carries no kernel name
(``kernel_metadata={}``): `kernel_kind` tells the stacked kernels apart by
their operand and result shapes.  The host line that runs Python carries
the benchmark's own spans (``bench.*``, from
`jax.profiler.TraceAnnotation`), among them ``bench.window`` around the
traced calls, and JAX's dispatch events (``PjitFunction(...)``).  All
events share one clock.

`reduce` keeps, for each device, the operations and program executions
that overlap the window, clipped to it, and the host events of the lines
that carry the benchmark's spans; the helpers below compute busy time as
the union of operation intervals, idle gaps and what the host was doing in
each.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
SHAPE = re.compile(r"f32\[([\d,]*)\]")
CONTROL_FLOW = re.compile(r"^%?(while|conditional|call)\b")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


@dataclass
class Event:
    name: str
    start: int          # ns
    end: int            # ns

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclass
class Device:
    index: int
    ops: list[Event] = field(default_factory=list)
    modules: list[Event] = field(default_factory=list)


@dataclass
class Reduced:
    window: tuple[int, int]
    devices: list[Device]
    spans: list[Event]          # the benchmark's spans, ``bench.*``
    host: list[Event] = field(default_factory=list)   # other host events

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(busy_ns(d) for d in self.devices) \
            / len(self.devices) * 1e-9


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _events(line, window=None):
    for ev in line.events:
        start = int(ev.start_ns)
        end = start + int(ev.duration_ns)
        if window is not None:
            if end <= window[0] or start >= window[1]:
                continue
            start, end = max(start, window[0]), min(end, window[1])
        yield ev, start, end


def reduce(path: str, device_ids=None) -> Reduced:
    """Read one trace file into a `Reduced`: the benchmark's host spans,
    and for each device (all of them, or those in ``device_ids``) the
    operations and program executions that overlap the window, clipped
    to it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, host = [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            evs = [Event(ev.name, start, end)
                   for ev, start, end in _events(line)]
            if any(e.name.startswith(SPAN_PREFIX) for e in evs):
                for e in evs:
                    (spans if e.name.startswith(SPAN_PREFIX)
                     else host).append(e)
    wins = [s for s in spans if s.name == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(wins)}")
    window = (wins[0].start, wins[0].end)
    devices = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m or (device_ids is not None
                     and int(m.group(2)) not in device_ids):
            continue
        dev = Device(int(m.group(2)))
        lines = {OPS_LINE: dev.ops, MODULES_LINE: dev.modules}
        for line in plane.lines:
            dst = lines.get(line.name)
            if dst is None:
                continue
            for ev, start, end in _events(line, window):
                dst.append(Event(ev.name, start, end))
        devices.append(dev)
    devices.sort(key=lambda d: d.index)
    host = [e for e in host if e.end > window[0] and e.start < window[1]]
    return Reduced(window, devices, sorted(spans, key=lambda s: s.start),
                   sorted(host, key=lambda s: s.start))


def union(intervals) -> list[tuple[int, int]]:
    """Merge (start, end) intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(dev: Device) -> int:
    """Nanoseconds in which some operation ran on the device."""
    return sum(e - s for s, e in union((o.start, o.end) for o in dev.ops))


def gaps(dev: Device, window: tuple[int, int]) -> list[tuple[int, int]]:
    """Idle intervals of the device within the window."""
    out, t = [], window[0]
    for s, e in union((o.start, o.end) for o in dev.ops):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if window[1] > t:
        out.append((t, window[1]))
    return out


def _innermost(events: list[Event], t: int, skip=()) -> Event | None:
    best = None
    for e in events:
        if e.start <= t < e.end and e.name not in skip:
            if best is None or e.dur < best.dur:
                best = e
    return best


def host_label(red: Reduced, t: int) -> str:
    """What the host was doing at ``t``: the innermost benchmark span (not
    the window) and the innermost dispatch event inside it."""
    span = _innermost(red.spans, t, skip=(WINDOW_SPAN,))
    inner = _innermost(red.host, t)
    label = span.name if span else "bench.between_calls"
    return f"{label} > {inner.name}" if inner else label


def matching(events, pattern: str) -> list[Event]:
    """Events whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.name)]


def kernel_kind(op_name: str) -> str | None:
    """Which stacked crossbar kernel (`kernels/ops.py`) an ``XLA Ops``
    event is, from its HLO text, or None for any other operation.

    * ``train``: `crossbar_train_stacked`, operands (g+, g-, x, d) and
      results (y, dx, g+', g-');
    * ``fwd``: `crossbar_fwd_stacked`, operands (x (T,M,K), g+ (T,K,N),
      g-) and result (T,M,N);
    * ``bwd``: `crossbar_bwd_stacked`, operands (d (T,M,N), g+, g-) and
      result (T,M,K);
    * ``dw``: `crossbar_dw_stacked`, operands (x (T,M,K), d (T,M,N)) and
      result (T,K,N)."""
    if "custom-call(" not in op_name or "tpu_custom_call" not in op_name:
        return None
    head, _, rest = op_name.partition("custom-call(")
    outs = [tuple(map(int, m.split(","))) for m in SHAPE.findall(head)
            if m]
    ins = [tuple(map(int, m.split(","))) for m in
           SHAPE.findall(rest.split("custom_call_target")[0]) if m]
    if len(outs) == 4 and len(ins) == 4 and outs[2] == ins[0]:
        return "train"
    if len(outs) == 1 and len(ins) == 3 and len(ins[1]) == 3:
        x, g, y = ins[0], ins[1], outs[0]
        if x[2] == g[1] and y[2] == g[2]:
            return "fwd"
        if x[2] == g[2] and y[2] == g[1]:
            return "bwd"
    if len(outs) == 1 and len(ins) == 2 and len(outs[0]) == 3 \
            and outs[0] == (ins[0][0], ins[0][2], ins[1][2]):
        return "dw"
    return None


def kernel_ns(dev: Device, kind: str) -> int:
    """Device nanoseconds of one kind of kernel."""
    return sum(o.dur for o in dev.ops if kernel_kind(o.name) == kind)


def short_name(op_name: str) -> str:
    """An operation's instruction name, with the kernel it is."""
    inst = op_name.split(" = ", 1)[0].lstrip("%")
    kind = kernel_kind(op_name)
    return f"{kind}_kernel ({inst})" if kind else inst


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device operations that took most time (summed over devices,
    divided by the device count; control flow, which nests the operations
    of its body, left out) and the longest idle gaps of device 0, each
    labelled with what the host was doing in its middle."""
    n = max(len(red.devices), 1)
    by_op: dict[str, int] = {}
    for dev in red.devices:
        for o in dev.ops:
            if CONTROL_FLOW.match(o.name):
                continue
            k = short_name(o.name)
            by_op[k] = by_op.get(k, 0) + o.dur
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    idle = []
    if red.devices:
        g = sorted(gaps(red.devices[0], red.window),
                   key=lambda se: se[0] - se[1])[:top]
        idle = [[host_label(red, (s + e) // 2), (e - s) * 1e-9]
                for s, e in g]
    return {"device_ops": [[k, v * 1e-9 / n] for k, v in ops],
            "idle_gaps": idle}
