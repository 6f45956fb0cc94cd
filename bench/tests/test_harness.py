"""The harness refuses to measure without a chip, and the trace reduction
on hand-made intervals."""
import json
import os
import subprocess
import sys

import pytest

from bench import trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_no_accelerator_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "mnist_class.chip_train.b64", "--seed", "3000000021",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        assert not line.startswith("{"), line


def reduced():
    ev = tr.Event
    dev = tr.Device(0, ops=[ev("a", 10, 20), ev("b", 15, 30),
                            ev("c", 50, 60)],
                    modules=[ev("jit_chip_train", 10, 30),
                             ev("jit_x", 50, 60)])
    spans = [ev(tr.WINDOW_SPAN, 0, 100), ev("bench.call", 0, 40),
             ev("bench.wait", 40, 70), ev("bench.feed", 70, 100)]
    return tr.Reduced((0, 100), [dev], spans)


def test_busy_idle_and_gaps():
    red = reduced()
    dev = red.devices[0]
    assert tr.union([(10, 20), (15, 30), (50, 60)]) == [(10, 30), (50, 60)]
    assert tr.busy_ns(dev) == 30
    assert tr.gaps(dev, red.window) == [(0, 10), (30, 50), (60, 100)]
    assert red.busy_s() == pytest.approx(30e-9)


def test_breakdown_labels_gaps_by_host_span():
    out = tr.breakdown(reduced())
    assert out["device_ops"][0] == ["b", pytest.approx(15e-9)]
    assert out["idle_gaps"] == [["bench.feed", pytest.approx(40e-9)],
                                ["bench.wait", pytest.approx(20e-9)],
                                ["bench.call", pytest.approx(10e-9)]]
    assert len(json.dumps(out)) < 4000
