"""The conv training cell (``lenet5_mnist.chip_conv_train.b64``) and the
serial ISOLET training cell (``isolet_class.chip_train.b64``): whole runs
on the CPU at a small size with the sound program and with faults planted
underneath, the controls at the conv cell's own size, and the conv
cell's work counts and readers.

Faults planted in the conv program, each caught by its number:

  * ``position0_update``: each conv stage's update is taken from the
    first position of every sample only (``grad_gap``);
  * ``patches_shifted``: every patch reads one lane further along its
    map (``err_gap_median``);
  * ``pool_error_whole``: the pool hands its error back to every lane of
    a window without dividing by the window (``grad_gap``).

The small conv network is LeNet-5's structure: a 14x14x1 input, 3x3
kernels of 2 and 3 maps, 2x2 pools, a dense 12 -> 8 -> 4, on 20 x 4
cores, at a learning rate of 1.0: at the cell's 0.1 a network this small
moves its conv kernels by less than half a pulse in three steps, and
the comparison could not see their updates."""
import json
import os
import types

import jax
import numpy as np
import pytest

from bench import reference as ref
from bench import reference_conv
from bench import run as harness
from bench import trace as tr
from bench import work
from bench import work_conv
from bench.kinds import train

CONV = "lenet5_mnist.chip_conv_train.b64"
ISOLET = "isolet_class.chip_train.b64"
SEED = 3_000_000_011
SEEDS = (3_000_000_101, 3_000_000_102, 3_000_000_103)
SMALL = {
    CONV: {"config": {"conv": [{"in": [14, 14, 1], "k": 3, "out": 2,
                                "pool": 2},
                               {"in": [6, 6, 2], "k": 3, "out": 3,
                                "pool": 2}],
                      "dims": [12, 8, 4], "core_rows": 20, "core_cols": 4},
           "traffic": {"pool": 4, "lr": 1.0}},
    ISOLET: {"config": {"dims": [30, 40, 100, 10], "core_rows": 40,
                        "core_cols": 10},
             "traffic": {"pool": 4}},
}


def one_run(workload):
    res = harness.run(workload, SEED, 0.5, False, require_chip=False,
                      overrides=SMALL[workload])
    return res["correct"], res["checks"]


def load(kind, name):
    with open(os.path.join(harness.BENCH, kind, name + ".json")) as f:
        return json.load(f)


def failed(checks):
    return {k for k, c in checks.items() if not c["value"] <= c["limit"]}


@pytest.fixture
def fresh_programs():
    """A planted fault reaches the program only through a new trace, so
    no compiled program outlives its test."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("workload", [CONV, ISOLET])
def test_sound_program_is_correct(workload):
    ok, checks = one_run(workload)
    assert ok, checks
    if workload == CONV:
        assert {"err_gap_median", "grad_gap", "change_gap", "xval"} \
            == set(checks)


def test_position0_update_is_caught(monkeypatch, fresh_programs):
    from repro.sim import compiled as csim
    orig = csim._stage_update
    batch = 64

    def from_position0(gp_s, gm_s, xs, ds, cfg, lr_eff):
        dxs, gp2, gm2 = orig(gp_s, gm_s, xs, ds, cfg, lr_eff)
        if xs.shape[2] > batch:                 # a conv stage's rows
            p = xs.shape[2] // batch
            _, gp2, gm2 = orig(gp_s, gm_s, xs[:, :, ::p], ds[:, :, ::p],
                               cfg, lr_eff)
        return dxs, gp2, gm2

    monkeypatch.setattr(csim, "_stage_update", from_position0)
    ok, checks = one_run(CONV)
    assert "grad_gap" in failed(checks), checks


def test_patches_shifted_is_caught(monkeypatch, fresh_programs):
    from repro.sim import placer
    orig = placer.conv_patch_idx

    def shifted(s, rows):
        idx = orig(s, rows)
        idx[:, 1:s.fan_in + 1] = np.minimum(idx[:, 1:s.fan_in + 1] + 1,
                                            s.in_size)
        return idx

    monkeypatch.setattr(placer, "conv_patch_idx", shifted)
    ok, checks = one_run(CONV)
    assert "err_gap_median" in failed(checks), checks


def test_pool_error_whole_is_caught(monkeypatch, fresh_programs):
    import jax.numpy as jnp

    from repro.sim import compiled as csim

    def whole(e, s):
        C, M = e.shape[0], e.shape[1]
        Ho, Wo = s.out_hw
        p = s.pool
        e = e.reshape(C, M, Ho // p, 1, Wo // p, 1, s.out)
        e = jnp.broadcast_to(e, (C, M, Ho // p, p, Wo // p, p, s.out))
        return e.reshape(C, M * s.positions, s.out)

    monkeypatch.setattr(csim, "_unpool", whole)
    ok, checks = one_run(CONV)
    assert "grad_gap" in failed(checks), checks


@pytest.mark.parametrize("seed", SEEDS)
def test_conv_control_is_not_correct(seed):
    """At the cell's own size, the reference one precision below the
    configuration's and each reference fault read not correct under the
    committed limits."""
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        w = next(w for w in json.load(f)["workloads"] if w["name"] == CONV)
    cfg, tr_ = load("configs", w["config"]), load("traffic", w["traffic"])
    limits = load("limits", CONV)
    spec, lr = ref.Spec(cfg), float(tr_["lr"])
    geom = reference_conv.geometry(cfg)
    g0 = [tuple(np.asarray(a) for a in p)
          for p in reference_conv.initial_conv_layers(cfg, seed)] \
        + [tuple(np.asarray(a) for a in p)
           for p in ref.initial_layers(cfg, seed)]
    (H, W, C), b = cfg["conv"][0]["in"], int(tr_["batch"])
    xs = ref.uniform_pool(seed, 1, (train.REF_STEPS, b, H * W * C))
    ts = ref.uniform_pool(seed, 2, (train.REF_STEPS, b, cfg["dims"][-1]))
    batches = [(xs[i], ts[i]) for i in range(train.REF_STEPS)]
    kind = harness.load_module(os.path.join(harness.BENCH, "kinds",
                                            "conv_train.py"))
    refd = train.reference_steps(g0, batches, lr, spec,
                                 step=kind.stepper(geom))

    def numbers(precision="highest", fault=None):
        return train.compare(g0, train.reference_steps(
            g0, batches, lr, spec, precision, kind.stepper(geom, fault)),
            refd)

    assert numbers("bf16_3x")["err_gap_median"] > limits["err_gap_median"]
    assert numbers(fault="position0")["grad_gap"] > limits["grad_gap"]
    assert numbers(fault="shifted")["err_gap_median"] \
        > limits["err_gap_median"]
    whole = numbers(fault="no_quarter")
    assert whole["grad_gap"] > limits["grad_gap"], whole
    assert whole["change_gap"] > limits["change_gap"], whole


def test_conv_work_by_hand():
    cfg = load("configs", "lenet5_mnist")
    assert work_conv.layers(cfg) == [(784, 25, 6), (100, 150, 16)]
    # 3 * (117,600 + 240,000 + 58,920) / 58,920
    assert work_conv.passes(cfg) == pytest.approx(3 * 416_520 / 58_920,
                                                  rel=1e-12)
    m = 64
    c1, c3 = 64 * 784 * 25 * 6, 64 * 100 * 150 * 16
    assert work_conv.fwd_work(cfg, m) == work.Work(
        2.0 * (c1 + c3),
        4 * (64 * 784 * 25 + 2 * 25 * 6 + 64 * 784 * 6
             + 64 * 100 * 150 + 2 * 150 * 16 + 64 * 100 * 16))
    # C1: the outer product only, no error handed down
    assert work_conv.train_work(cfg, m) == work.Work(
        2.0 * c1 + 4.0 * c3,
        4 * (4 * 25 * 6 + 64 * 784 * 25 + 64 * 784 * 6
             + 4 * 150 * 16 + 2 * 64 * 100 * 150 + 64 * 100 * 16))
    assert [work_conv.kernel_rows(m, p) for p in (784, 100)] == [50176,
                                                                 6400]
    assert work_conv.kernel_rows(1, 100) == 100
    assert work_conv.kernel_rows(2, 100) == 256


def _op(name_fmt, rows, k):
    return name_fmt.format(rows=rows, k=k)


TRAIN = ('%custom-call.3 = (f32[1,{rows},100]{{2,1,0}}, '
         'f32[1,{rows},{k}]{{2,1,0}}, f32[1,{k},100]{{2,1,0}}, '
         'f32[1,{k},100]{{2,1,0}}) custom-call(f32[1,{k},100]{{2,1,0}} %a, '
         'f32[1,{k},100]{{2,1,0}} %b, f32[1,{rows},{k}]{{2,1,0}} %c, '
         'f32[1,{rows},100]{{2,1,0}} %d), '
         'custom_call_target="tpu_custom_call"')
FWD = ('%custom-call.4 = f32[1,{rows},100]{{2,1,0}} custom-call('
       'f32[1,{rows},{k}]{{2,1,0}} %x, f32[1,{k},100]{{2,1,0}} %p, '
       'f32[1,{k},100]{{2,1,0}} %m), custom_call_target="tpu_custom_call"')
DENSE_FWD = ('%custom-call.5 = f32[4,64,100]{2,1,0} custom-call('
             'f32[4,64,400]{2,1,0} %x, f32[4,400,100]{2,1,0} %p, '
             'f32[4,400,100]{2,1,0} %m), '
             'custom_call_target="tpu_custom_call"')


#: the conv kernels as the v5e compile of the cell's program emits them:
#: one core, so the stacked kernels' core axis is gone, and the
#: conductances staged on-chip (``S(1)``)
S1 = "{1,0:T(8,128)S(1)}"
FWD_V5E = ('%vmap_crossbar_fwd_.9 = f32[50176,100]{1,0:T(8,128)} '
           'custom-call(f32[50176,400]{1,0:T(8,128)} %x, '
           f'f32[400,100]{S1} %p, f32[400,100]{S1} %m), '
           'custom_call_target="tpu_custom_call"')
TRAIN_V5E = ('%vmap_crossbar_train_.13 = (f32[6400,100]{1,0:T(8,128)}, '
             f'f32[6400,400]{S1}, f32[400,100]{S1}, f32[400,100]{S1}) '
             f'custom-call(f32[400,100]{S1} %a, f32[400,100]{S1} %b, '
             'f32[6400,400]{1,0:T(8,128)} %c, f32[6400,100]{1,0:T(8,128)} '
             '%d), custom_call_target="tpu_custom_call"')


def reading(ops, calls=2, cfg=None):
    ev = tr.Event
    red = tr.Reduced((0, 10**9),
                     [tr.Device(0, ops=[ev(*o) for o in ops])],
                     [ev(tr.WINDOW_SPAN, 0, 10**9)], [])
    return types.SimpleNamespace(
        red=red, calls=calls, samples=64 * calls,
        cfg=cfg or load("configs", "lenet5_mnist"), chips=1,
        peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def reader(name):
    return harness.load_module(os.path.join(harness.BENCH, "metrics",
                                            name + ".py"))


def test_conv_readers_on_hand_made_intervals():
    ops = [(_op(FWD, 50176, 400), 0, 300_000),
           (_op(FWD, 6400, 400), 300_000, 400_000),
           (DENSE_FWD, 400_000, 450_000),
           (_op(TRAIN, 50176, 400), 500_000, 900_000),
           (_op(TRAIN, 6400, 400), 900_000, 1_000_000)]
    assert [tr.kernel_kind(o[0]) for o in ops] == ["fwd"] * 3 \
        + ["train"] * 2
    r = reading(ops)
    cfg = r.cfg
    # the dense stage's kernel is not a conv stage's
    assert len(work_conv.conv_kernels(r.red.devices, "fwd", cfg, 64)) == 2
    least_f, _ = (work_conv.fwd_work(cfg, 64) * 2).least_seconds(r.peak)
    least_t, _ = (work_conv.train_work(cfg, 64) * 2).least_seconds(r.peak)
    assert reader("conv_fwd_kernel_roofline").read(r) == pytest.approx(
        100 * least_f / 400e-6)
    assert reader("conv_train_kernel_roofline").read(r) == pytest.approx(
        100 * least_t / 500e-6)


def test_conv_kernels_as_the_v5e_compile_emits_them():
    """Rank-2 operands (the core axis gone), told apart from the dense
    stages' kernels by their folded rows; the bytes of what lies on-chip
    are not counted."""
    rows = {50176, 6400}
    assert work_conv.conv_kind(FWD_V5E, rows) == "fwd"
    assert work_conv.conv_kind(TRAIN_V5E, rows) == "train"
    assert work_conv.conv_kind(FWD_V5E, {64}) is None
    assert work_conv.conv_kind(DENSE_FWD, rows | {64}) is None
    r = reading([(FWD_V5E, 0, 300_000), (TRAIN_V5E, 300_000, 400_000)])
    fwd = work_conv.conv_kernels(r.red.devices, "fwd", r.cfg, 64)
    train = work_conv.conv_kernels(r.red.devices, "train", r.cfg, 64)
    assert len(fwd) == len(train) == 1
    assert work_conv.hbm_shares(fwd, "fwd") == [1.0, 0.0, 0.0, 1.0]
    assert work_conv.hbm_shares(train, "train") == [0.0, 0.0, 1.0, 1.0,
                                                    1.0, 0.0, 0.0, 0.0]
    least, _ = (work_conv.fwd_work(r.cfg, 64, [1.0, 0.0, 0.0, 1.0]) * 2) \
        .least_seconds(r.peak)
    assert reader("conv_fwd_kernel_roofline").read(r) == pytest.approx(
        100 * least / 300e-6)
    assert reader("conv_train_kernel_roofline").read(r) > 0


@pytest.mark.parametrize("name", ["conv_fwd_kernel_roofline",
                                  "conv_train_kernel_roofline"])
def test_conv_readers_return_none_with_nothing_to_read(name):
    assert reader(name).read(reading([("%fusion.1 = f32[] fusion()",
                                       0, 10)])) is None
    assert reader(name).read(reading([(DENSE_FWD, 0, 10)])) is None
    assert reader(name).read(reading([(_op(FWD, 50176, 400), 0, 10)],
                                     calls=0)) is None
    # a configuration without conv layers (the parent's cells)
    assert reader(name).read(reading([(_op(FWD, 50176, 400), 0, 10)],
                                     cfg=load("configs",
                                              "mnist_class"))) is None
