"""The two cells of greedy pretraining and recognition
(``isolet_ae.pipeline_pretrain.l1.b64``, ``mnist_class.chip_infer.b1024``):
whole runs on the CPU at a small size with the sound program and with
faults planted underneath, the control at each cell's own size, and the
pretraining cell's work counts and readers.

Faults planted in the pretraining program, each caught by its number:

  * ``prefix_updated``: the backward program of the encoder's modelled
    chip starts at its first stage, so the frozen encoder 0 is updated
    too (``frozen_gap``);
  * ``target_before_adc``: the target is the representation before its
    3-bit ADC (``err_gap_median``);
  * ``boundary_error_lost``: no error crosses back from the decoder's
    modelled chip to the encoder's (``grad_gap``).

At the small size, as at the cell's, the path of hidden layer 1 splits
over the two modelled chips as [encoder 0, encoder 1 | decoder 1]."""
import json
import os
import types

import numpy as np
import pytest

from bench import reference as ref
from bench import reference_ae
from bench import run as harness
from bench import trace as tr
from bench import work
from bench import work_pretrain
from bench.kinds import train

PRETRAIN = "isolet_ae.pipeline_pretrain.l1.b64"
INFER = "mnist_class.chip_infer.b1024"
SEED = 3_000_000_011
SEEDS = (3_000_000_101, 3_000_000_102, 3_000_000_103)
SMALL = {
    PRETRAIN: {"config": {"dims": [30, 40, 100, 10], "core_rows": 40,
                          "core_cols": 10},
               "traffic": {"pool": 4}},
    INFER: {"config": {"dims": [100, 40, 10], "core_rows": 40,
                       "core_cols": 10},
            "traffic": {"pool": 4, "batch": 64}},
}
ADC_STEP = 1.0 / 7.0          # one step of the 3-bit output ADC


def one_run(workload):
    res = harness.run(workload, SEED, 0.5, False, require_chip=False,
                      overrides=SMALL[workload])
    return res["correct"], res["checks"]


def load(kind, name):
    with open(os.path.join(harness.BENCH, kind, name + ".json")) as f:
        return json.load(f)


def cell(workload):
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        w = next(w for w in json.load(f)["workloads"]
                 if w["name"] == workload)
    return load("configs", w["config"]), load("traffic", w["traffic"]), \
        load("limits", workload)


def failed(checks):
    return {k for k, c in checks.items() if not c["value"] <= c["limit"]}


def test_small_path_splits_as_the_cells_does():
    from repro.core.mapping import map_pretrain_path, split_network
    for cfg in (SMALL[PRETRAIN]["config"], load("configs", "isolet_ae")):
        nmap = map_pretrain_path(cfg["dims"], 1, cfg["core_rows"],
                                 cfg["core_cols"])
        assert split_network(nmap, n_chips=2) == ((0, 1), (2,))


@pytest.mark.parametrize("workload", [PRETRAIN, INFER])
def test_sound_program_is_correct(workload):
    ok, checks = one_run(workload)
    assert ok, checks
    if workload == PRETRAIN:
        assert checks["frozen_gap"]["value"] == 0.0


def test_prefix_updated_is_caught(monkeypatch):
    from repro.sim import compiled as csim
    orig = csim.chip_backward

    def from_the_first_stage(*args, first=0, **kw):
        return orig(*args, **kw)

    monkeypatch.setattr(csim, "chip_backward", from_the_first_stage)
    ok, checks = one_run(PRETRAIN)
    assert "frozen_gap" in failed(checks), checks


def test_target_before_adc_is_caught(monkeypatch):
    from repro.core.crossbar import hard_sigmoid
    from repro.sim.fabric import ChipPipeline

    def before_adc(wave, first):
        _, dps = wave
        return hard_sigmoid(dps[first - 1])

    monkeypatch.setattr(ChipPipeline, "_pretrain_target",
                        staticmethod(before_adc))
    ok, checks = one_run(PRETRAIN)
    assert "err_gap_median" in failed(checks), checks


def test_boundary_error_lost_is_caught(monkeypatch):
    import jax.numpy as jnp

    from repro.sim import compiled as csim
    orig = csim.chip_backward

    def no_handoff(*args, **kw):
        gp2, gm2, delta, cnt = orig(*args, **kw)
        return gp2, gm2, jnp.zeros_like(delta), cnt

    monkeypatch.setattr(csim, "chip_backward", no_handoff)
    ok, checks = one_run(PRETRAIN)
    assert "grad_gap" in failed(checks), checks
    assert checks["frozen_gap"]["value"] == 0.0


def test_altered_answer_is_caught(monkeypatch):
    from repro.sim import compiled as csim
    orig = csim.chip_infer

    def altered(*args, **kw):
        out, cnt = orig(*args, **kw)
        return out.at[0].add(ADC_STEP), cnt

    monkeypatch.setattr(csim, "chip_infer", altered)
    ok, checks = one_run(INFER)
    assert "out_gap_max" in failed(checks), checks


@pytest.mark.parametrize("seed", SEEDS)
def test_pretraining_control_is_not_correct(seed):
    """At the cell's own size, the reference one precision below the
    configuration's, and each reference fault, read not correct under
    the committed limits."""
    import jax.numpy as jnp
    cfg, tr_, limits = cell(PRETRAIN)
    kind = harness.load_module(os.path.join(harness.BENCH, "kinds",
                                            "pretrain.py"))
    spec = ref.Spec(cfg)
    k, lr = int(tr_["layer"]), float(tr_["lr"])
    enc = [tuple(np.asarray(a) for a in p)
           for p in ref.initial_layers(cfg, seed)]
    dec = reference_ae.initial_decoders(cfg, seed)[k]
    g0 = [enc[k], tuple(np.asarray(a) for a in dec)]
    prefix = tuple((jnp.asarray(a), jnp.asarray(b)) for a, b in enc[:k])
    xs = ref.uniform_pool(seed, 1, (train.REF_STEPS, int(tr_["batch"]),
                                    cfg["dims"][0]))
    batches = [(xs[i], None) for i in range(train.REF_STEPS)]
    refd = train.reference_steps(g0, batches, lr, spec,
                                 step=kind.sound(prefix))

    def numbers(precision="highest", fault=kind.sound):
        return train.compare(g0, train.reference_steps(
            g0, batches, lr, spec, precision, fault(prefix)), refd)

    ctl = numbers("bf16_3x")
    assert ctl["err_gap_median"] > limits["err_gap_median"], ctl
    assert numbers(fault=kind.target_before_adc)["err_gap_median"] \
        > limits["err_gap_median"]
    assert numbers(fault=kind.boundary_error_zeroed)["grad_gap"] \
        > limits["grad_gap"]
    half = numbers(fault=kind.half_batch)
    assert half["grad_gap"] > limits["grad_gap"], half
    assert half["change_gap"] > limits["change_gap"], half


@pytest.mark.parametrize("seed", SEEDS)
def test_recognition_control_is_not_correct(seed):
    cfg, tr_, limits = cell(INFER)
    spec = ref.Spec(cfg)
    g0 = ref.initial_layers(cfg, seed)
    x = ref.uniform_pool(seed, 1, (int(tr_["batch"]), cfg["dims"][0]))
    got = np.asarray(ref.forward(g0, x, spec, "bf16_3x"), np.float64)
    gaps = ref.nearest_gaps(got, *ref.forward_candidates(g0, x, spec))
    assert float(np.median(gaps)) > limits["out_gap_median"]
    assert float(np.max(gaps)) > limits["out_gap_max"]


def test_pretraining_work_by_hand():
    cfg = load("configs", "isolet_ae")
    assert work_pretrain.path_dims(cfg["dims"], 1) == [617, 2000, 1000,
                                                       2000]
    # (617*2000 + 3*(2000*1000 + 1000*2000)) / 3,864,000
    assert work_pretrain.passes(cfg["dims"], 1) == pytest.approx(
        13_234_000 / 3_864_000, rel=1e-12)
    assert work.network_ops(cfg["dims"], passes=1) == 2 * 3_864_000
    # real main-grid cores: encoder 0 40, encoder 1 60, decoder 1 60
    n_fwd, n_train = 40 + 60 + 60, 60 + 60
    R, C = 400, 100
    assert work_pretrain.fwd_work(cfg, 1, 64) == work.Work(
        n_fwd * 2.0 * 64 * R * C, n_fwd * 4 * (2.0 * R * C + 64 * (R + C)))
    assert work_pretrain.train_work(cfg, 1, 64) == work.Work(
        n_train * 4.0 * 64 * R * C,
        n_train * 4 * (4.0 * R * C + 2.0 * 64 * R + 64 * C))


TRAIN = ('%custom-call.3 = (f32[60,64,100]{2,1,0}, f32[60,64,400]{2,1,0}, '
         'f32[60,400,100]{2,1,0}, f32[60,400,100]{2,1,0}) custom-call('
         'f32[60,400,100]{2,1,0} %a, f32[60,400,100]{2,1,0} %b, '
         'f32[60,64,400]{2,1,0} %c, f32[60,64,100]{2,1,0} %d), '
         'custom_call_target="tpu_custom_call"')
FWD = ('%custom-call.4 = f32[60,64,100]{2,1,0} custom-call('
       'f32[60,64,400]{2,1,0} %x, f32[60,400,100]{2,1,0} %p, '
       'f32[60,400,100]{2,1,0} %m), custom_call_target="tpu_custom_call"')


def reading(ops, host=(), calls=2, modules=()):
    ev = tr.Event
    red = tr.Reduced((0, 10**9),
                     [tr.Device(0, ops=[ev(*o) for o in ops],
                                modules=[ev(*m) for m in modules])],
                     [ev(tr.WINDOW_SPAN, 0, 10**9)],
                     [ev(*h) for h in host])
    return types.SimpleNamespace(
        red=red, calls=calls, samples=64 * calls,
        cfg=load("configs", "isolet_ae"), traffic={"layer": 1}, chips=1,
        work=work, peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def reader(name):
    return harness.load_module(os.path.join(harness.BENCH, "metrics",
                                            name + ".py"))


def test_pretraining_readers_on_hand_made_intervals():
    assert (tr.kernel_kind(TRAIN), tr.kernel_kind(FWD)) == ("train", "fwd")
    r = reading([(TRAIN, 0, 400_000), (FWD, 500_000, 600_000)],
                host=[("repro.pipeline.pretrain_step", 0, 800_000),
                      ("repro.pipeline.pretrain_step", 900_000, 10**9)])
    least_t, _ = (work_pretrain.train_work(r.cfg, 1, 64) * 2) \
        .least_seconds(r.peak)
    least_f, _ = (work_pretrain.fwd_work(r.cfg, 1, 64) * 2) \
        .least_seconds(r.peak)
    assert reader("ae_train_kernel_roofline").read(r) == pytest.approx(
        100 * least_t / 400e-6)
    assert reader("ae_fwd_kernel_roofline").read(r) == pytest.approx(
        100 * least_f / 100e-6)
    # idle inside the spans: [400, 500) and [600, 800) us of the first,
    # all of the second
    assert reader("pretrain_idle_ms_per_call").read(r) == pytest.approx(
        (0.3 + (10**9 - 900_000) * 1e-6) / 2)


S1 = "{2,1,0:T(8,128)S(1)}"
TRAIN_ON_CHIP = (
    f'%vmap_crossbar_train_.2 = (f32[60,64,100]{{2,1,0:T(8,128)}}, '
    f'f32[60,64,400]{S1}, f32[60,400,100]{S1}, f32[60,400,100]{S1}) '
    f'custom-call(f32[60,400,100]{S1} %a, f32[60,400,100]{S1} %b, '
    f'f32[60,64,400]{S1} %c, f32[60,64,100]{S1} %d), '
    'custom_call_target="tpu_custom_call"')
FWD_ON_CHIP = (
    f'%vmap_crossbar_fwd_.7 = f32[60,64,100]{S1} custom-call('
    f'f32[60,64,400]{S1} %x, f32[60,400,100]{S1} %p, '
    f'f32[60,400,100]{S1} %m), custom_call_target="tpu_custom_call"')


def test_kernel_work_counts_only_what_lies_in_hbm():
    """Operands and results in on-chip memory (``S(1)``, as the v5e
    compile of the path's programs places them) move no HBM bytes: with
    all of them on-chip the least time is the operations' own, and with
    the forward kernel's on-chip in one execution of two, its bytes are
    half of `bench/work.py`'s."""
    assert work_pretrain.in_hbm(TRAIN) == [True] * 8
    assert work_pretrain.in_hbm(TRAIN_ON_CHIP) == [False] * 4 + [True] \
        + [False] * 3
    r = reading([(TRAIN_ON_CHIP, 0, 400_000), (FWD_ON_CHIP, 400_000,
                                                500_000),
                 (FWD, 500_000, 600_000)])
    assert work_pretrain.hbm_shares(r.red.devices, "fwd") == [0.5] * 4
    m, cfg = 64, r.cfg
    flops_t = work_pretrain.train_work(cfg, 1, m).flops * 2
    assert work_pretrain.train_work(
        cfg, 1, m, work_pretrain.hbm_shares(r.red.devices, "train")) \
        == work.Work(flops_t / 2, 0.0)
    assert reader("ae_train_kernel_roofline").read(r) == pytest.approx(
        100 * flops_t / r.peak["flops_per_s"] / 400e-6)
    full = work_pretrain.fwd_work(cfg, 1, m)
    half = work_pretrain.fwd_work(cfg, 1, m, [0.5] * 4)
    assert half == work.Work(full.flops, full.bytes / 2)
    least_f, _ = (half * 2).least_seconds(r.peak)
    assert reader("ae_fwd_kernel_roofline").read(r) == pytest.approx(
        100 * least_f / 200e-6)


@pytest.mark.parametrize("name", ["ae_train_kernel_roofline",
                                  "ae_fwd_kernel_roofline",
                                  "pretrain_idle_ms_per_call"])
def test_pretraining_readers_return_none_with_nothing_to_read(name):
    assert reader(name).read(reading([("%fusion.1 = f32[] fusion()",
                                       0, 10)])) is None
    assert reader(name).read(reading([(TRAIN, 0, 10), (FWD, 10, 20)],
                                     calls=0)) is None
