"""The program's host spans and the readers that assign the device's idle
time to them (`bench/spans.py`, `bench/metrics/*_per_call.py`).

Hand-made intervals check each reader, and a traced window of each cell
on the CPU at a small size checks where the program puts its spans."""
import os
import types

import pytest

from bench import run as harness
from bench import spans
from bench import trace as tr
from bench import work
from bench.tests.test_faults import SEED, small

IDLE = {"prepare_idle_ms_per_call": spans.PREPARE,
        "dispatch_idle_ms_per_call": spans.DISPATCH,
        "writeback_idle_ms_per_call": spans.WRITEBACK,
        "account_idle_ms_per_call": spans.ACCOUNT}
READERS = [*IDLE, "syncs_per_call"]
CELLS = ["mnist_class.chip_train.b64", "isolet_class.pipeline_serve.q64",
         "isolet_class.pipeline_train.b64"]


def reader(name):
    return harness.load_module(os.path.join(harness.BENCH, "metrics",
                                            name + ".py"))


def reading(red, calls):
    return types.SimpleNamespace(red=red, calls=calls)


def hand_made(host, devices=True):
    """A 1000 ns window with two calls; the device is busy 100-200 and
    600-700."""
    ev = tr.Event
    dev = tr.Device(0, ops=[ev("op", 100, 200), ev("op", 600, 700)])
    return tr.Reduced((0, 1000), [dev] if devices else [],
                      [ev(tr.WINDOW_SPAN, 0, 1000)],
                      [ev(n, s, e) for n, s, e in host])


CALLS = [("repro.chip.train_step", 0, 450),
         ("repro.prepare", 0, 120), ("repro.dispatch", 120, 150),
         ("repro.writeback", 150, 300), ("repro.sync", 300, 320),
         ("repro.account", 320, 350), ("repro.sync", 350, 400),
         ("repro.account", 400, 450),
         ("repro.chip.train_step", 500, 1000),
         ("repro.prepare", 500, 650), ("repro.dispatch", 650, 680),
         ("repro.writeback", 680, 900), ("repro.sync", 900, 950),
         ("repro.account", 950, 1000)]


@pytest.mark.parametrize("name,idle_ns", [
    ("prepare_idle_ms_per_call", 100 + 100),
    ("dispatch_idle_ms_per_call", 0 + 0),
    ("writeback_idle_ms_per_call", 100 + 200),
    ("account_idle_ms_per_call", 80 + 50)])
def test_idle_readers_on_hand_made_intervals(name, idle_ns):
    red = hand_made(CALLS)
    assert reader(name).read(reading(red, 2)) == pytest.approx(
        idle_ns * 1e-6 / 2)


def test_syncs_per_call_on_hand_made_intervals():
    assert reader("syncs_per_call").read(reading(hand_made(CALLS), 2)) \
        == pytest.approx(1.5)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_zero_for_a_phase_without_spans(name):
    phase = IDLE.get(name, spans.SYNC)
    red = hand_made([c for c in CALLS if c[0] != phase])
    assert reader(name).read(reading(red, 2)) == 0.0


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_none_without_entry_span_or_device(name):
    r = reader(name)
    no_entry = [c for c in CALLS if c[0] in spans.PHASES]
    assert r.read(reading(hand_made(no_entry), 2)) is None
    assert r.read(reading(hand_made(CALLS, devices=False), 2)) is None
    assert r.read(reading(hand_made([]), 2)) is None


def inside(inner, outer) -> bool:
    return outer.start <= inner.start and inner.end <= outer.end


@pytest.fixture(scope="module")
def cpu_windows():
    """One traced window of each cell on the CPU at a small size: the
    reduced trace and the run's result."""
    mp = pytest.MonkeyPatch()
    kept = {}
    reduce = tr.reduce

    def keep(*args, **kw):
        kept["red"] = reduce(*args, **kw)
        return kept["red"]

    mp.setattr(tr, "reduce", keep)
    # the CPU has no entry in the table of peaks; `mfu` reads one
    mp.setattr(work, "peak", lambda kind: {"flops_per_s": 197e12,
                                           "hbm_bytes_per_s": 819e9})
    out = {}
    try:
        for cell in CELLS:
            res = harness.run(cell, SEED, 0.3, True, require_chip=False,
                              overrides=small(cell))
            out[cell] = (kept.pop("red"), res)
    finally:
        mp.undo()
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_one_outermost_entry_span_per_call(cpu_windows, cell):
    red, res = cpu_windows[cell]
    assert res["correct"], res["checks"]
    calls = [s for s in red.spans if s.name == "bench.call"]
    entries = spans.entries(red)
    outer = [e for e in entries
             if not any(o is not e and inside(e, o) for o in entries)]
    assert len(calls) > 1
    assert len(outer) == len(calls)
    for c in calls:
        assert sum(inside(e, c) for e in outer) == 1


@pytest.mark.parametrize("cell", CELLS)
def test_phase_spans_nest_in_entries_only(cpu_windows, cell):
    red, _ = cpu_windows[cell]
    entries = spans.entries(red)
    phases = [e for e in red.host if e.name in spans.PHASES]
    assert {p.name for p in phases} >= {spans.PREPARE, spans.DISPATCH,
                                        spans.WRITEBACK, spans.ACCOUNT}
    for p in phases:
        assert any(inside(p, e) for e in entries), p
        assert not any(q is not p and inside(p, q) for q in phases), p


@pytest.mark.parametrize("cell", CELLS)
def test_every_dispatch_in_an_entry_sits_in_one_phase(cpu_windows, cell):
    red, _ = cpu_windows[cell]
    entries = spans.entries(red)
    phases = [e for e in red.host if e.name in spans.PHASES]
    work_events = [e for e in red.host
                   if e.name.startswith(("PjitFunction(", "DevicePut"))
                   and any(inside(e, x) for x in entries)]
    assert work_events
    for e in work_events:
        assert sum(inside(e, p) for p in phases) == 1, e


@pytest.mark.parametrize("cell,syncs", [
    ("mnist_class.chip_train.b64", 2),
    ("isolet_class.pipeline_train.b64", 4)])
def test_syncs_per_train_call_on_the_cpu(cpu_windows, cell, syncs):
    # one read of each counter block per modelled chip and pass
    red, _ = cpu_windows[cell]
    calls = sum(s.name == "bench.call" for s in red.spans)
    assert sum(e.name == spans.SYNC for e in red.host) == syncs * calls
