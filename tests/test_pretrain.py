"""Layer-wise autoencoder pretraining on the virtual chip and the chip
pipeline (paper sections III.C-E): `pretrain_step` against the plain
reference (`core.autoencoder`), frozen prefixes, a whole greedy schedule
handed to supervised fine-tuning, and the counters against
`hw_model.pretrain_step_cost`.

The network is small, [12, 30, 8, 5] on 8 x 4 cores, so every layer
splits over fan-in tiles and each pair's path (frozen encoders, encoder,
decoder) splits over two modelled chips.  Conductances are seeded
uniform over the whole range, so every layer's update is many pulses.

Tolerances, and why:

  * outputs and errors agree to `ATOL`: the chip sums each layer over
    its fan-in tiles (Fig. 14) where the reference makes one contraction,
    and float32 re-association moves a sum by a few ulps;
  * conductances agree to one half pulse (`HALF_PULSE`) on at most
    `PULSE_SHARE` of a leaf's elements, and exactly elsewhere: a pulse
    update rounds ``dw`` to whole pulses, so a few-ulp change of ``dw``
    moves an element by one pulse only where ``dw`` lies at a rounding
    boundary, and the pulses are written half to each device of a pair.
    The pipeline and the serial chip run different programs (each slice
    has its own envelope), so they are held to the same tolerance, never
    bitwise.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.paper_apps import PAPER_SPEC
from repro.core import autoencoder as ae
from repro.core import crossbar as xb
from repro.core import hw_model as hw
from repro.sim import ChipPipeline, VirtualChip
from repro.sim import compiled as csim

pytestmark = pytest.mark.sim

DIMS = [12, 30, 8, 5]
ROWS, COLS = 8, 4
SPEC = dataclasses.replace(PAPER_SPEC, rows=ROWS, cols=COLS)
LR = 0.1
ATOL = 1e-5
HALF_PULSE = 0.5 * SPEC.max_update / SPEC.update_levels
PULSE_SHARE = 0.01


def _uniform(key, f, o):
    kp, km = jax.random.split(key)
    return {"g_plus": jax.random.uniform(kp, (f, o)),
            "g_minus": jax.random.uniform(km, (f, o))}


def _net(seed=0):
    key = jax.random.PRNGKey(seed)
    pairs = list(zip(DIMS, DIMS[1:]))
    enc = [_uniform(jax.random.fold_in(key, i), f, o)
           for i, (f, o) in enumerate(pairs)]
    dec = [_uniform(jax.random.fold_in(key, 100 + i), o, f)
           for i, (f, o) in enumerate(pairs)]
    return enc, dec


def _x(n=16, seed=5):
    return jax.random.uniform(jax.random.PRNGKey(seed), (n, DIMS[0]),
                              minval=-0.5, maxval=0.5)


def _build(kind, enc, dec):
    if kind == "chip":
        return VirtualChip(enc, SPEC, rows=ROWS, cols=COLS, name="ae",
                           decoders=dec)
    return ChipPipeline(enc, SPEC, rows=ROWS, cols=COLS, name="ae",
                        n_chips=2, decoders=dec)


def _representation(encoders, x, layer):
    """What enters encoder ``layer``, as `ae.pretrain_stack` feeds it."""
    r = x
    for enc in encoders[:layer]:
        r = xb.crossbar_apply(enc, r, SPEC, transport_in=False)
        r = xb.q.adc_quantize_ste(r, SPEC.adc_bits)
    return r


def assert_pulse_close(got, want):
    """Equal but for a rare element one half pulse away (module doc)."""
    for k in ("g_plus", "g_minus"):
        d = np.abs(np.asarray(got[k], np.float64)
                   - np.asarray(want[k], np.float64))
        assert d.max() <= HALF_PULSE * 1.001, (k, d.max())
        assert np.mean(d > 1e-6) <= PULSE_SHARE, (k, np.mean(d > 1e-6))


def _frozen(obj, layer):
    return [{k: np.asarray(v) for k, v in p.items()}
            for p in obj.layers()[:layer]]


def test_pipeline_splits_each_pair_path_over_two_chips():
    enc, dec = _net()
    pipe = _build("pipeline", enc, dec)
    paths = [pipe._pretrain_path(k) for k in range(3)]
    # pair 1 crosses the boundary between encoder 1 and its decoder; the
    # decoder's error is handed back into chip 0
    assert [p.groups for p in paths] == [((0,), (1,)), ((0,), (1, 2)),
                                         ((0,), (1, 2, 3))]
    assert [p._owner(k) for k, p in enumerate(paths)] == [(0, 0), (1, 0),
                                                           (1, 1)]


@pytest.mark.parametrize("kind", ["chip", "pipeline"])
def test_pretrain_step_matches_reference_step_by_step(kind):
    """Each layer in turn, three steps each, against `ae._ae_bp` on the
    reference representation of the object's own (frozen) encoders; the
    prefix stays bitwise as it was after every step."""
    enc, dec = _net()
    obj = _build(kind, enc, dec)
    x = _x()
    for layer in range(len(DIMS) - 1):
        for _ in range(3):
            pre = _frozen(obj, layer)
            r = _representation(obj.layers(), x, layer)
            (want_e, want_d), want_err = ae._ae_bp(
                obj.layers()[layer], obj.decoders()[layer], r, SPEC, LR)
            others = [obj.decoders()[j] for j in range(len(DIMS) - 1)
                      if j != layer]
            err = obj.pretrain_step(layer, x, LR)
            np.testing.assert_allclose(err, want_err, atol=ATOL)
            assert_pulse_close(obj.layers()[layer], want_e)
            assert_pulse_close(obj.decoders()[layer], want_d)
            for a, b in zip(pre, _frozen(obj, layer)):
                for k in a:
                    assert np.array_equal(a[k], b[k]), (layer, k)
            for a, b in zip(others, [obj.decoders()[j]
                                     for j in range(len(DIMS) - 1)
                                     if j != layer]):
                assert np.array_equal(a["g_plus"], b["g_plus"])


@pytest.mark.parametrize("kind", ["chip", "pipeline"])
def test_a_path_sees_every_later_write(kind):
    """A pair's path built earlier reads the encoders as other writes left
    them: a pretraining step of an earlier layer, or a supervised step of
    the whole network on the same object."""
    enc, dec = _net(seed=7)
    obj = _build(kind, enc, dec)
    x = _x(seed=9)
    tgt = jax.random.uniform(jax.random.PRNGKey(10), (x.shape[0], DIMS[-1]),
                             minval=-0.5, maxval=0.5)
    obj.pretrain_step(1, x, LR)
    for write in ("pretrain", "train"):
        if write == "pretrain":
            obj.pretrain_step(0, x, LR)
        else:
            obj.train_step(x, tgt, LR)
        r = _representation(obj.layers(), x, 1)
        (want_e, _), want_err = ae._ae_bp(obj.layers()[1],
                                          obj.decoders()[1], r, SPEC, LR)
        np.testing.assert_allclose(obj.pretrain_step(1, x, LR), want_err,
                                   atol=ATOL)
        assert_pulse_close(obj.layers()[1], want_e)


def test_pipeline_agrees_with_the_serial_chip():
    enc, dec = _net(seed=3)
    chip, pipe = _build("chip", enc, dec), _build("pipeline", enc, dec)
    x = _x(seed=8)
    for layer in (1, 2, 1):
        for _ in range(2):
            e_chip = chip.pretrain_step(layer, x, LR)
            e_pipe = pipe.pretrain_step(layer, x, LR)
            np.testing.assert_allclose(e_pipe, e_chip, atol=ATOL)
    for got, want in zip(pipe.layers() + pipe.decoders(),
                         chip.layers() + chip.decoders()):
        assert_pulse_close(got, want)


def test_one_program_per_pair():
    enc, dec = _net(seed=4)
    chip = _build("chip", enc, dec)
    x = _x(n=6)
    csim.reset_trace_counts()
    for layer in (0, 1, 0, 1, 1):
        chip.pretrain_step(layer, x, LR)
    keys = [k for k in csim.trace_counts() if k[0] == "chip_train"]
    assert sorted(k[-2] for k in keys) == [0, 1], keys
    assert all(k[-1] is True and csim.trace_counts()[k] == 1 for k in keys)


def test_greedy_schedule_then_finetune_matches_reference(monkeypatch):
    """The paper's workflow: each hidden layer pretrained greedily
    (`ae.pretrain_stack`'s initial conductances and batch order, driven
    step by step through `VirtualChip.pretrain_step`), then the encoders
    fine-tuned with supervised back-propagation on a new chip
    (`ae.finetune_supervised`'s order, through `train_step`).  Both sides
    draw the initial conductances over the whole range, so that every
    layer moves (at the paper's initial 0.02 w_max the deeper pairs take
    no pulse in a few steps)."""
    monkeypatch.setattr(xb, "init_conductances",
                        lambda key, f, o, spec: _uniform(key, f, o))
    key, key_ft = jax.random.PRNGKey(11), jax.random.PRNGKey(12)
    n, batch, epochs, lr = 32, 8, 2, 0.5
    x = _x(n=n, seed=13)
    y = jax.random.uniform(jax.random.PRNGKey(14), (n, DIMS[-1]),
                           minval=-0.5, maxval=0.5)
    want, _ = ae.pretrain_stack(key, x, DIMS, SPEC, lr=lr, epochs=epochs,
                                batch=batch)

    def order(k):
        for ek in jax.random.split(k, epochs):
            perm = jax.random.permutation(ek, n)
            yield from perm[:(n // batch) * batch].reshape(-1, batch)

    enc, dec = [], []
    for k, (f, h) in zip(jax.random.split(key, len(DIMS) - 1),
                         zip(DIMS, DIMS[1:])):
        kenc, kdec = jax.random.split(k)
        enc.append(xb.init_conductances(kenc, f, h, SPEC))
        dec.append(xb.init_conductances(kdec, h, f, SPEC))
    chip = _build("chip", enc, dec)
    for layer, k in enumerate(jax.random.split(key, len(DIMS) - 1)):
        for idx in order(jax.random.split(k)[1]):
            chip.pretrain_step(layer, x[idx], lr)
    for got, w, e0 in zip(chip.layers(), want, enc):
        assert not np.array_equal(w["g_plus"], e0["g_plus"])
        assert_pulse_close(got, w)

    tuned, _ = ae.finetune_supervised(key_ft, want, x, y, SPEC, lr=lr,
                                      epochs=epochs, batch=batch)
    fine = VirtualChip(chip.layers(), SPEC, rows=ROWS, cols=COLS)
    for idx in order(key_ft):
        fine.train_step(x[idx], y[idx], lr)
    for got, w in zip(fine.layers(), tuned):
        assert_pulse_close(got, w)


@pytest.mark.parametrize("kind", ["chip", "pipeline"])
def test_counters_match_pretrain_step_cost(kind):
    enc, dec = _net(seed=6)
    obj = _build(kind, enc, dec)
    x = _x(n=4)
    for layer in (0, 1, 2, 2):
        obj.pretrain_step(layer, x, LR)
    rep = obj.report()
    assert [p.layer for p in rep.pretrain] == [0, 1, 2]
    assert [p.samples for p in rep.pretrain] == [4, 4, 8]
    rel = {k: v for k, v in rep.compare_hw().items()
           if k.startswith("pretrain")}
    assert len(rel) == 3 * 6
    assert max(rel.values()) <= 0.01, rel
    split = {"n_chips": 2} if kind == "pipeline" else {}
    for p in rep.pretrain:
        cost = hw.pretrain_step_cost("ae", DIMS, p.layer, rows=ROWS,
                                     cols=COLS, **split)
        assert p.frozen_core_steps == p.samples * cost.frozen_cores
    if kind == "pipeline":
        # pair 1: [enc0 | enc1, dec1] — activations cross the boundary
        # forward, and no error crosses back into the frozen chip
        p1 = rep.pretrain[1]
        assert (p1.link_bits_fwd, p1.link_bits_bwd) == (30 * 3, 0)
        p0 = rep.pretrain[0]
        assert (p0.link_bits_fwd, p0.link_bits_bwd) == (30 * 3, 30 * 8)


def test_pretraining_needs_decoders():
    enc, _ = _net()
    with pytest.raises(ValueError, match="decoders"):
        VirtualChip(enc, SPEC, rows=ROWS, cols=COLS).pretrain_step(
            0, _x(), LR)
    with pytest.raises(ValueError, match="decoder 1"):
        enc, dec = _net()
        dec[1] = dec[0]
        VirtualChip(enc, SPEC, rows=ROWS, cols=COLS, decoders=dec)
