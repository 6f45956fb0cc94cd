"""The trace reduction on a trace recorded on a TPU v5e: five
`VirtualChip.train_step` calls of ``mnist_class`` at batch 64 under the
benchmark's spans (``data/chip_train.xplane.pb``)."""
import collections
import os

import pytest

from bench import trace as tr

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "chip_train.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return tr.reduce(TRACE)


def test_window_spans_and_devices(red):
    assert [d.index for d in red.devices] == [0]
    names = collections.Counter(s.name for s in red.spans)
    assert names == {"bench.window": 1, "bench.call": 5, "bench.wait": 5}
    assert red.window_s == pytest.approx(0.06698356)


def test_program_executions(red):
    progs = collections.Counter(m.name.split("(")[0]
                                for m in red.devices[0].modules)
    assert progs["jit_chip_train"] == 5
    assert sum(progs.values()) == 93


def test_kernels_found_by_shape(red):
    kinds = collections.Counter(tr.kernel_kind(o.name)
                                for o in red.devices[0].ops)
    # one fused train kernel and one forward kernel per stage per step
    assert kinds["train"] == 5 * 4 and kinds["fwd"] == 5 * 4
    assert tr.kernel_ns(red.devices[0], "train") == 115538
    assert tr.kernel_ns(red.devices[0], "fwd") == 47736


def test_busy_is_the_union_of_operations(red):
    dev = red.devices[0]
    busy = tr.busy_ns(dev)
    assert busy == 1391452
    idle = sum(e - s for s, e in tr.gaps(dev, red.window))
    assert busy + idle == red.window[1] - red.window[0]


def test_breakdown_names_kernels_and_host_work(red):
    out = tr.breakdown(red)
    names = [k for k, _ in out["device_ops"]]
    assert "train_kernel (vmap__.18)" in names
    assert not any(n.startswith("while") for n in names)
    assert len(out["idle_gaps"]) == 10
    assert all(label.startswith("bench.") for label, _ in out["idle_gaps"])
