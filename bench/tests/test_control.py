"""The control: the plain reference put in the program's place and
computed one precision below the configuration's (``bf16_3x``, the three
bfloat16 passes of `Precision.HIGH`, against float32 at
`Precision.HIGHEST`), at each cell's own size, must come out not correct
under the cell's committed limits.  The readings that set those limits on
the chip are made by ``bench/readings.py``; this test repeats the
control's side on the CPU, on three seeds."""
import json
import os

import numpy as np
import pytest

from bench import reference as ref
from bench.kinds import train

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (3_000_000_101, 3_000_000_102, 3_000_000_103)


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def cell(workload):
    bench = load("..", "BENCHMARK")
    w = next(w for w in bench["workloads"] if w["name"] == workload)
    return load("configs", w["config"]), load("traffic", w["traffic"]), \
        load("limits", workload)


def failed(numbers, limits):
    return [k for k, v in numbers.items() if v > limits[k]]


@pytest.mark.parametrize("workload", ["mnist_class.chip_train.b64",
                                      "isolet_class.pipeline_train.b64"])
@pytest.mark.parametrize("seed", SEEDS)
def test_training_control_is_not_correct(workload, seed):
    cfg, tr, limits = cell(workload)
    spec = ref.Spec(cfg)
    g0 = [tuple(np.asarray(a) for a in p)
          for p in ref.initial_layers(cfg, seed)]
    b, d = int(tr["batch"]), cfg["dims"]
    xs = ref.uniform_pool(seed, 1, (train.REF_STEPS, b, d[0]))
    ts = ref.uniform_pool(seed, 2, (train.REF_STEPS, b, d[-1]))
    batches = [(xs[i], ts[i]) for i in range(train.REF_STEPS)]
    refd = train.reference_steps(g0, batches, float(tr["lr"]), spec)
    ctl = train.reference_steps(g0, batches, float(tr["lr"]), spec,
                                "bf16_3x")
    assert failed(train.compare(g0, ctl, refd), limits)


@pytest.mark.parametrize("seed", SEEDS)
def test_serving_control_is_not_correct(seed):
    cfg, tr, limits = cell("isolet_class.pipeline_serve.q64")
    spec = ref.Spec(cfg)
    g0 = ref.initial_layers(cfg, seed)
    x = ref.uniform_pool(seed, 1, (int(tr["requests"]), cfg["dims"][0]))
    got = np.asarray(ref.forward(g0, x, spec, "bf16_3x"), np.float64)
    gaps = ref.nearest_gaps(got, *ref.forward_candidates(g0, x, spec))
    numbers = {"out_gap_median": float(np.median(gaps)),
               "out_gap_max": float(gaps.max())}
    assert failed(numbers, limits)
