"""Conv networks on the virtual chip (DESIGN.md "Conv stages"):
`VirtualChip(conv=...)` against the plain reference `core.conv`, its
counters against `hw_model.conv_network_cost`, and the dense path left as
it was.

The network is LeNet-5's structure at a small size: a 14x14x1 input, a
3x3 kernel of 2 maps (12x12x2), a 2x2 pool (6x6x2), a 3x3 kernel of 3 maps
(4x4x3), a 2x2 pool (2x2x3 = 12 lanes), then a dense 12 -> 8 -> 4, on
20 x 4 cores, at a batch of 4 and a learning rate of 1.0.  Conductances
are seeded uniform over the whole range, so every layer's update is many
pulses (at a rate of 0.1 a batch of 4 moves no layer but the last by
half a pulse in three steps).

Tolerances, and why:

  * outputs and errors agree to `ATOL`: the chip sums each contraction
    over padded core lines and in another order than the reference, and
    float32 re-association moves a sum by a few ulps;
  * conductances agree to one half pulse (`HALF_PULSE`) on at most
    `PULSE_SHARE` of a leaf's elements, and exactly elsewhere: a pulse
    update rounds ``dw`` to whole pulses, so a few-ulp change of ``dw``
    moves an element by one pulse only where ``dw`` lies at a rounding
    boundary, and the pulses are written half to each device of a pair.
"""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_apps import NETWORKS, PAPER_SPEC
from repro.core import conv as cref
from repro.core import crossbar as xb
from repro.core import hw_model as hw
from repro.core.mapping import ConvShape, map_conv_network
from repro.sim import VirtualChip
from repro.sim import compiled as csim
from repro.sim.placer import conv_col2im_idx, conv_patch_idx

pytestmark = pytest.mark.sim

SHAPES = (ConvShape((14, 14, 1), 3, 2, 2), ConvShape((6, 6, 2), 3, 3, 2))
DIMS = [12, 8, 4]
ROWS, COLS = 20, 4
SPEC = dataclasses.replace(PAPER_SPEC, rows=ROWS, cols=COLS)
BATCH, LR = 4, 1.0
ATOL = 1e-5
HALF_PULSE = 0.5 * SPEC.max_update / SPEC.update_levels
PULSE_SHARE = 0.01


def _uniform(key, f, o):
    kp, km = jax.random.split(key)
    return {"g_plus": jax.random.uniform(kp, (f, o)),
            "g_minus": jax.random.uniform(km, (f, o))}


def _net(seed=0):
    key = jax.random.PRNGKey(seed)
    conv = [_uniform(jax.random.fold_in(key, i), s.fan_in, s.out)
            for i, s in enumerate(SHAPES)]
    dense = [_uniform(jax.random.fold_in(key, 10 + i), f, o)
             for i, (f, o) in enumerate(zip(DIMS, DIMS[1:]))]
    return conv, dense


def _batch(seed, width):
    return jax.random.uniform(jax.random.PRNGKey(seed), (BATCH, width),
                              minval=-0.5, maxval=0.5)


def _chip(conv, dense):
    return VirtualChip(conv + dense, SPEC, rows=ROWS, cols=COLS,
                       name="lenet_small", conv=list(SHAPES))


def _host(layers):
    return [{k: np.asarray(v) for k, v in p.items()} for p in layers]


def assert_pulse_close(got, want):
    """Equal but for a rare element one half pulse away (module doc)."""
    for k in ("g_plus", "g_minus"):
        d = np.abs(np.asarray(got[k], np.float64)
                   - np.asarray(want[k], np.float64))
        assert d.max() <= HALF_PULSE * 1.001, (k, d.max())
        assert np.mean(d > 1e-6) <= PULSE_SHARE, (k, np.mean(d > 1e-6))


def test_lenet5_maps_to_ten_cores():
    nmap = map_conv_network(list(cref.LENET5_CONV), list(cref.LENET5_DIMS))
    # C1 1, C3 1, C5 4 + 2 aggregation, F6 1, output 1
    assert nmap.cores == 10
    assert [c.positions for c in nmap.conv] == [784, 100]
    assert [c.core.fan_in for c in nmap.conv] == [25, 150]
    assert [c.routed_codes for c in nmap.conv] == [784 * 6, 100 * 16]
    assert [c.pool.sums for c in nmap.conv] == [14 * 14 * 6, 5 * 5 * 16]
    assert nmap.dims == (1024, 1176, 400, 120, 84, 10)


def test_mismatched_conv_chain_is_refused():
    with pytest.raises(ValueError):
        map_conv_network(list(SHAPES), [13, 4])


def test_patch_map_is_the_reference_im2col():
    """The position-indexed gather over [bias, map] is `core.conv.patches`
    (bias line 0, zero lines past the patch), and the transposed map sums
    back exactly what `core.conv.col2im` sums."""
    for s in SHAPES:
        h = np.asarray(_batch(3, s.in_size))
        ext = np.concatenate([np.zeros((BATCH, 1), np.float32), h], 1)
        got = ext[:, conv_patch_idx(s, ROWS)]          # (B, P, rows)
        want = np.asarray(cref.patches(jnp.asarray(h), s)).reshape(
            BATCH, s.positions, s.fan_in)
        np.testing.assert_array_equal(got[:, :, 1:s.fan_in + 1], want)
        assert not got[:, :, 0].any() and not got[:, :, s.fan_in + 1:].any()
        e = np.asarray(_batch(4, s.positions * ROWS)).reshape(
            BATCH, s.positions, ROWS)
        flat = np.concatenate([e.reshape(BATCH, -1),
                               np.zeros((BATCH, 1), np.float32)], 1)
        ci = conv_col2im_idx(s, ROWS)
        got = flat[:, ci[:, 0]]
        for j in range(1, s.k * s.k):
            got = got + flat[:, ci[:, j]]
        want = cref.col2im(jnp.asarray(e[:, :, 1:s.fan_in + 1].reshape(
            -1, s.fan_in)), s)
        np.testing.assert_array_equal(got, np.asarray(want))


@pytest.mark.parametrize("seed", [0, 1])
def test_infer_matches_reference(seed):
    conv, dense = _net(seed)
    x = _batch(100 + seed, SHAPES[0].in_size)
    want = cref.forward(conv, dense, x, SHAPES, SPEC)
    got = _chip(conv, dense).infer(x)
    assert got.shape == (BATCH, DIMS[-1])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)


@pytest.mark.parametrize("seed", [0, 1])
def test_three_train_steps_match_reference(seed):
    conv, dense = _net(seed)
    chip = _chip(conv, dense)
    rconv, rdense = _host(conv), _host(dense)
    for k in range(3):
        x = _batch(200 + 10 * seed + k, SHAPES[0].in_size)
        t = _batch(300 + 10 * seed + k, DIMS[-1])
        rconv, rdense, rerr = cref.train_step(rconv, rdense, x, t, LR,
                                              SHAPES, SPEC)
        err = chip.train_step(x, t, LR)
        np.testing.assert_allclose(np.asarray(err), np.asarray(rerr),
                                   atol=ATOL)
        got = chip.layers()
        assert len(got) == len(SHAPES) + len(DIMS) - 1
        for g, w in zip(got, list(rconv) + list(rdense)):
            assert g["g_plus"].shape == w["g_plus"].shape
            assert_pulse_close(g, w)
    # every layer moved, the first conv layer too
    for g, g0 in zip(chip.layers(), conv + dense):
        assert np.abs(np.asarray(g["g_plus"])
                      - np.asarray(g0["g_plus"])).max() > 0


def test_kernel_body_matches_reference(monkeypatch):
    """The Pallas kernel body (interpret mode here, as on the chip's
    compile) takes the conv stages' folded rows: one step and a
    recognition pass against the reference."""
    monkeypatch.setenv("REPRO_SIM_FORCE_KERNELS", "1")
    conv, dense = _net(2)
    chip = _chip(conv, dense)
    assert chip._cfg.use_kernels
    x, t = _batch(7, SHAPES[0].in_size), _batch(8, DIMS[-1])
    rconv, rdense, rerr = cref.train_step(_host(conv), _host(dense), x, t,
                                          LR, SHAPES, SPEC)
    np.testing.assert_allclose(np.asarray(chip.train_step(x, t, LR)),
                               np.asarray(rerr), atol=ATOL)
    for g, w in zip(chip.layers(), list(rconv) + list(rdense)):
        assert_pulse_close(g, w)
    np.testing.assert_allclose(
        np.asarray(chip.infer(x)),
        np.asarray(cref.forward(rconv, rdense, x, SHAPES, SPEC)), atol=ATOL)


def test_one_program_a_step():
    conv, dense = _net(0)
    chip = _chip(conv, dense)
    x, t = _batch(1, SHAPES[0].in_size), _batch(2, DIMS[-1])
    chip.train_step(x, t, LR)
    before = csim.trace_counts()
    for _ in range(2):
        chip.train_step(x, t, LR)
    assert csim.trace_counts() == before


@pytest.mark.parametrize("mode", ["infer", "train"])
def test_counters_match_the_cost_model(mode):
    conv, dense = _net(0)
    chip = _chip(conv, dense)
    x, t = _batch(1, SHAPES[0].in_size), _batch(2, DIMS[-1])
    for _ in range(2):
        if mode == "infer":
            chip.infer(x)
        else:
            chip.train_step(x, t, LR)
    cost = hw.conv_network_cost("lenet_small", list(SHAPES), DIMS,
                                rows=ROWS, cols=COLS)
    rel = chip.report().compare_hw(cost)
    keys = [k for k in rel if k.startswith((mode, "conv"))]
    assert {f"conv{l}_{q}" for l in range(2)
            for q in ("core_steps", "routed_codes", "pool_sums")} \
        <= set(keys)
    assert max(rel[k] for k in keys) <= 0.01, rel
    # a conv core is stepped once per position in each phase
    per = chip.report().conv
    assert [c["core_steps"] for c in per] == [144, 16]


def test_time_multiplexed_cost_by_hand():
    cost = hw.conv_network_cost("lenet_small", list(SHAPES), DIMS,
                                rows=ROWS, cols=COLS)
    # 144 + 16 conv core steps and 2 dense layers a phase; routed: the
    # conv codes (288, 48), the pooled values (72, 12), the dense
    # layers' outputs (8, 4)
    slots = 144 + 16 + 2
    route_us = (288 + 72 + 48 + 12 + 8 + 4) / hw.ROUTING_CLOCK_HZ * 1e6
    assert cost.infer.time_us == pytest.approx(slots * hw.FWD_US + route_us)
    assert cost.train.time_us == pytest.approx(
        slots * (hw.FWD_US + hw.BWD_US + hw.UPD_US) + route_us)
    assert cost.cores == 1 + 1 + 2 + 1
    assert [c.pool_sums for c in cost.conv] == [72, 12]


def test_slice_programs_refuse_a_conv_network():
    conv, dense = _net(0)
    chip = _chip(conv, dense)
    with pytest.raises(NotImplementedError):
        chip.forward_wave(_batch(1, SHAPES[0].in_size))


#: sha256 of the index maps of `StageStacks` for ``mnist_class`` and
#: ``isolet_class`` on the paper's cores, as they were before conv stages
#: came: a dense network's envelope is unchanged by them
DENSE_INDEX_MAPS = {
    "mnist_class":
        "19a60407777c51a271ed3f49e52ab850348732ecc5874afcae8acfffb1487160",
    "isolet_class":
        "f9478ebfe709196ed146c80358e7e226474b0ef939eeb55f69ab062adc5354f3",
}


@pytest.mark.parametrize("net", sorted(DENSE_INDEX_MAPS))
def test_dense_index_maps_are_unchanged(net):
    dims = NETWORKS[net]
    key = jax.random.PRNGKey(0)
    layers = [xb.init_conductances(jax.random.fold_in(key, i), f, o,
                                   PAPER_SPEC)
              for i, (f, o) in enumerate(zip(dims, dims[1:]))]
    st = VirtualChip(layers, PAPER_SPEC)._get_stacks()
    idx = st.index_pytree()
    assert "conv_patch" not in idx and st.conv == ()
    h = hashlib.sha256()
    for k, v in sorted(idx.items()):
        h.update(k.encode())
        h.update(np.asarray(v).tobytes())
    assert h.hexdigest() == DENSE_INDEX_MAPS[net]
