"""Operations and bytes against hand counts, and the table of peaks."""
import json
import os

import pytest

from bench import work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,recognition", [("mnist_class", 632_400),
                                              ("isolet_class", 7_731_000)])
def test_network_ops_match_table_one(name, recognition):
    dims = config(name)["dims"]
    assert work.network_ops(dims, passes=1) == recognition
    assert work.network_ops(dims, passes=3) == 3 * recognition


@pytest.mark.parametrize("name,cores", [("mnist_class", [6, 2, 1, 1]),
                                        ("isolet_class", [40, 60, 15, 6, 1])])
def test_real_cores_are_the_placed_main_grid(name, cores):
    assert work.real_cores(config(name)) == cores


def test_kernel_work_by_hand():
    cfg = {"dims": [7, 5], "core_rows": 4, "core_cols": 3}
    # (7 + 1 bias) / 4 -> 2 fan-in tiles, 5 / 3 -> 2 fan-out tiles
    assert work.real_cores(cfg) == [4]
    f = work.fwd_work(cfg, 2)
    assert f.flops == 4 * 2 * 2 * 4 * 3
    assert f.bytes == 4 * 4 * (2 * 12 + 2 * 4 + 2 * 3)
    t = work.train_work(cfg, 2)
    assert t.flops == 4 * 4 * 2 * 4 * 3
    assert t.bytes == 4 * 4 * (4 * 12 + 2 * 2 * 4 + 2 * 3)


def test_least_time_names_its_bound():
    peak = work.peak("TPU v5 lite")
    secs, bound = work.Work(flops=197e12, bytes=1.0).least_seconds(peak)
    assert (secs, bound) == (1.0, "compute")
    secs, bound = work.Work(flops=1.0, bytes=819e9).least_seconds(peak)
    assert (secs, bound) == (1.0, "bandwidth")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peak("cpu")
