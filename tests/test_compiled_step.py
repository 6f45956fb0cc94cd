"""Compiled whole-step execution (DESIGN.md §8), the simulator's only
execution path.

Four contracts:

  * the fused training megakernel `crossbar_train_stacked` equals the
    four-call sequence (fwd + bwd + dw + pulse) BITWISE over a sweep of
    shapes and ragged zero-padded core stacks, including the 8-bit
    sign-magnitude error path;
  * the compiled chip/farm/serve paths equal the plain reference
    (`core.crossbar.mlp_forward` / `paper_backprop_step`) numerically, a
    chip with device faults trains on the same programs, and every
    counter equals the value the static schedule gives EXACTLY;
  * compilation happens exactly once per (topology, batch) shape, the
    conductance stacks are donated (updated in place, allocation-stable);
  * the kernel-side caches are bounded LRUs and the autotune table
    persists/reloads.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as hst

from repro.configs.paper_apps import PAPER_SPEC
from repro.core import crossbar as xb, hw_model as hw
from repro.core import quantization as q
from repro.kernels import ops as kernel_ops
from repro.sim import VirtualChip, compiled as csim
from repro.sim.cluster import build_farm
from repro.sim.placer import build_stage_stacks, place_network

pytestmark = pytest.mark.sim


def _layers(dims, seed=0, spec=PAPER_SPEC):
    key = jax.random.PRNGKey(seed)
    return [xb.init_conductances(jax.random.fold_in(key, i), f, o, spec)
            for i, (f, o) in enumerate(zip(dims, dims[1:]))]


def _x(dims, n=4, seed=9):
    return jax.random.uniform(jax.random.PRNGKey(seed), (n, dims[0]),
                              minval=-0.5, maxval=0.5)


# ---------------------------------------------------------------------------
# Megakernel differential: fused == four-call sequence, bitwise
# ---------------------------------------------------------------------------

def _four_call(gp, gm, xs, ds, *, lr, dy_scale=None):
    """The dispatch-per-phase reference the megakernel must reproduce."""
    if dy_scale is not None:
        ds_deq = ds.astype(jnp.float32) * dy_scale
    else:
        ds_deq = ds
    ys = kernel_ops.crossbar_fwd_stacked(xs, gp, gm)
    dxs = kernel_ops.crossbar_bwd_stacked(ds_deq, gp, gm)
    gp2, gm2 = kernel_ops.pulse_update_stacked(
        gp, gm, xs, ds_deq, lr=lr, max_dw=PAPER_SPEC.max_update,
        levels=PAPER_SPEC.update_levels, w_max=PAPER_SPEC.w_max)
    return ys, dxs, gp2, gm2


def _assert_megakernel_matches(T, M, K, N, seed, *, err_bits=None,
                               ragged=0):
    k = jax.random.PRNGKey(seed)
    gp = jax.random.uniform(jax.random.fold_in(k, 0), (T, K, N),
                            minval=0.1, maxval=0.9)
    gm = jax.random.uniform(jax.random.fold_in(k, 1), (T, K, N),
                            minval=0.1, maxval=0.9)
    xs = jax.random.normal(jax.random.fold_in(k, 2), (T, M, K))
    ds = jax.random.normal(jax.random.fold_in(k, 3), (T, M, N)) * 0.2
    if ragged:
        # zero-padded trailing cores: the StageStacks envelope discipline
        zero = jnp.zeros((ragged,) + gp.shape[1:])
        gp = jnp.concatenate([gp[:-ragged], zero])
        gm = jnp.concatenate([gm[:-ragged], zero])
        xs = jnp.concatenate([xs[:-ragged], jnp.zeros_like(xs[:ragged])])
        ds = jnp.concatenate([ds[:-ragged], jnp.zeros_like(ds[:ragged])])
    scale = None
    if err_bits is not None:
        qt = q.error_quantize(ds, err_bits)
        ds, scale = qt.codes.astype(jnp.float32), qt.scale
    ys, dxs, gp2, gm2 = kernel_ops.crossbar_train_stacked(
        gp, gm, xs, ds, lr=0.05, dy_scale=scale,
        max_dw=PAPER_SPEC.max_update, levels=PAPER_SPEC.update_levels,
        w_max=PAPER_SPEC.w_max, compute_y=True)
    ry, rdx, rgp, rgm = _four_call(gp, gm, xs, ds, lr=0.05, dy_scale=scale)
    np.testing.assert_array_equal(np.asarray(ys), np.asarray(ry))
    np.testing.assert_array_equal(np.asarray(dxs), np.asarray(rdx))
    np.testing.assert_array_equal(np.asarray(gp2), np.asarray(rgp))
    np.testing.assert_array_equal(np.asarray(gm2), np.asarray(rgm))


@pytest.mark.parametrize("T,M,K,N,err_bits,ragged", [
    (1, 2, 17, 9, None, 0),
    (3, 4, 41, 15, None, 0),
    (4, 2, 400, 100, None, 2),          # paper core geometry, ragged stack
    (3, 4, 41, 15, 8, 0),               # sign-magnitude error codes
    (5, 3, 129, 101, 8, 3),             # ragged + codes
])
def test_megakernel_matches_four_call_bitwise(T, M, K, N, err_bits, ragged):
    _assert_megakernel_matches(T, M, K, N, seed=7, err_bits=err_bits,
                               ragged=ragged)


@given(hst.integers(1, 5), hst.integers(1, 6), hst.integers(3, 64),
       hst.integers(2, 40), hst.booleans(), hst.integers(0, 2),
       hst.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_megakernel_matches_four_call_hypothesis(T, M, K, N, codes, ragged,
                                                 seed):
    ragged = min(ragged, T - 1)
    _assert_megakernel_matches(T, M, K, N, seed=seed,
                               err_bits=8 if codes else None, ragged=ragged)


def test_megakernel_compute_y_off_zeroes_forward():
    k = jax.random.PRNGKey(0)
    gp = jax.random.uniform(k, (2, 17, 9))
    gm = jnp.zeros_like(gp)
    xs = jax.random.normal(jax.random.fold_in(k, 1), (2, 3, 17))
    ds = jax.random.normal(jax.random.fold_in(k, 2), (2, 3, 9))
    ys, _, _, _ = kernel_ops.crossbar_train_stacked(
        gp, gm, xs, ds, lr=0.01, compute_y=False)
    assert float(jnp.abs(ys).max()) == 0.0


# ---------------------------------------------------------------------------
# Compiled paths == the plain reference (chip, farm, serving); counters ==
# the static schedule
# ---------------------------------------------------------------------------

def _schedule(dims):
    """Per-stage (billed cores, emitting links, routed outputs) of the
    network's static schedule, from the mapper alone."""
    from repro.core.mapping import map_network
    return [(lm.total_cores, lm.row_tiles * lm.col_tiles, lm.routed_outputs)
            for lm in map_network(dims).layers]


def _assert_counters(cc, dims, M, *, phases, io_bits, calls=1):
    """``cc`` holds ``calls`` waves of ``M`` samples over ``phases``: S·M
    slots and M·Σ cores core steps a phase a wave, one NoC record per
    stage a wave, ``io_bits`` a sample."""
    from repro.sim.noc import LinkRecord
    sched = _schedule(dims)
    S, cores = len(sched), sum(c for c, _, _ in sched)
    for p in ("fwd", "bwd", "update"):
        n = calls if p in phases else 0
        assert cc.slots[p] == n * S * M, (p, cc.slots)
        assert cc.core_steps[p] == n * M * cores, (p, cc.core_steps)
    assert cc.samples == calls * M
    assert cc.io_bits == calls * M * io_bits
    assert cc.noc.records == calls * [
        LinkRecord(s, routed, links, M)
        for s, (_, links, routed) in enumerate(sched)]


@pytest.mark.parametrize("dims", [[41, 15, 41],
                                  hw.PAPER_NETWORKS["mnist_class"]])
def test_compiled_chip_matches_plain_reference(dims):
    layers = _layers(dims)
    M = 4
    x, tgt = _x(dims, n=M), _x(dims, n=M, seed=3)[:, :dims[-1]]
    chip = VirtualChip([dict(p) for p in layers], PAPER_SPEC)
    np.testing.assert_allclose(np.asarray(chip.infer(x)),
                               np.asarray(xb.mlp_forward(layers, x,
                                                         PAPER_SPEC)),
                               atol=1e-5)
    ref = [dict(p) for p in layers]
    for step, atol in enumerate((1e-6, 1e-5)):
        ref, ref_err = xb.paper_backprop_step(ref, x, tgt, PAPER_SPEC,
                                              lr=0.2)
        err = chip.train_step(x, tgt, lr=0.2)
        np.testing.assert_allclose(np.asarray(err), np.asarray(ref_err),
                                   atol=atol)
    for a, b in zip(chip.layers(), ref):
        for k in ("g_plus", "g_minus"):
            np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                       atol=1e-5)
    # accounting is schedule-derived, so it must be EXACTLY the schedule's
    out_bits = dims[-1] * hw.ADC_BITS_OUT
    _assert_counters(chip.infer_counters, dims, M, phases=("fwd",),
                     io_bits=dims[0] * chip.input_bits + out_bits)
    _assert_counters(chip.train_counters, dims, M,
                     phases=("fwd", "bwd", "update"), calls=2,
                     io_bits=2 * dims[0] * chip.input_bits + out_bits)


def test_compiled_farm_serve_matches_plain_reference():
    dims = hw.PAPER_NETWORKS["kdd_anomaly"]
    Q, C = 7, 2
    x = _x(dims, n=Q, seed=5)
    farm = build_farm("kdd_anomaly", C, seed=0)
    out, stats = farm.serve(x)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(xb.mlp_forward(farm.layers(), x, PAPER_SPEC)), atol=1e-5)
    chip = VirtualChip(farm.layers(), PAPER_SPEC)
    np.testing.assert_allclose(np.asarray(out), np.asarray(chip.infer(x)),
                               atol=1e-6)
    # the static schedule: request r enters lane r % C at beat r // C
    S, beat = len(dims) - 1, farm.beat_us
    q_max = -(-Q // C)
    assert stats == {"beats": S - 1 + q_max, "retired": Q, "beat_us": beat,
                     "makespan_us": (S - 1 + q_max) * beat,
                     "samples_per_s": Q / (q_max * beat) * 1e6,
                     "occupancy": Q * S / (S * C * (S - 1 + q_max))}
    bits = dims[0] * farm.input_bits + dims[-1] * hw.ADC_BITS_OUT
    for c, cc in enumerate(farm.chip_infer):
        _assert_counters(cc, dims, -(-(Q - c) // C), phases=("fwd",),
                         io_bits=bits)
    assert farm.serve_full_beats == Q // C
    assert farm.serve_link.sample_bits == Q * bits


def test_compiled_serve_keeps_cross_session_microbatch_contract():
    """A server pins one request microbatch over its lifetime: a second
    session with a different microbatch raises the documented error, read
    from the requests' host shapes before anything is served."""
    from repro.runtime.serve_loop import RequestQueue
    from repro.sim.cluster import FarmServer
    farm = build_farm("kdd_anomaly", 2, seed=0)
    server = FarmServer(farm)
    server.run(RequestQueue([jnp.zeros((2, 41))] * 4))      # m=2 session
    with pytest.raises(ValueError, match="uniform request shapes"):
        server.run(RequestQueue([jnp.zeros((3, 41))] * 4))  # m=3 rejected


def test_compiled_farm_train_matches_plain_reference():
    dims = hw.PAPER_NETWORKS["kdd_anomaly"]
    M, C = 8, 2
    x = _x(dims, n=M, seed=6)
    farm = build_farm("kdd_anomaly", C, seed=0)
    layers = [dict(p) for p in farm.layers()]
    chip = VirtualChip([dict(p) for p in layers], PAPER_SPEC)
    ef = farm.train_step(x, x, lr=0.1)
    ref, ref_err = xb.paper_backprop_step(layers, x, x, PAPER_SPEC, lr=0.1)
    np.testing.assert_allclose(np.asarray(ef), np.asarray(ref_err),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(ef),
                               np.asarray(chip.train_step(x, x, lr=0.1)),
                               atol=1e-6)
    for a, b, c in zip(farm.layers(), ref, chip.layers()):
        for k in ("g_plus", "g_minus"):
            np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                       atol=1e-6)
            np.testing.assert_allclose(np.asarray(a[k]), np.asarray(c[k]),
                                       atol=1e-6)
    assert farm.replicas_in_sync()
    bits = 2 * dims[0] * farm.input_bits + dims[-1] * hw.ADC_BITS_OUT
    for cc in farm.chip_train:
        _assert_counters(cc, dims, M // C, phases=("fwd", "bwd", "update"),
                         io_bits=bits)
    # every chip sends its dw codes up and takes the pulses back: 8 bits
    # a placed main-grid cell each way
    rows, cols = farm.placement.rows, farm.placement.cols
    cells = sum(links * rows * cols for _, links, _ in _schedule(dims))
    assert (farm.train_link.reconcile_bits
            == C * 2 * cells * hw.ERR_BITS_LINK)


def test_faulted_chip_trains_on_the_compiled_path(monkeypatch):
    """A chip that owns device faults trains through `chip_train`: each
    step equals the reference step followed by the stuck-mask re-assert,
    and the stuck cells hold after two steps."""
    from repro.runtime.faults import MemristorFaults
    from repro.sim.faults import inject_faults, reapply
    calls = _capture(monkeypatch, "chip_train")
    dims, w_max = [41, 15, 41], PAPER_SPEC.w_max
    f = MemristorFaults(stuck_on=0.05, stuck_off=0.2, variation_sigma=0.1,
                        seed=2)
    layers = _layers(dims)
    chip = VirtualChip([dict(p) for p in layers], PAPER_SPEC, faults=f)
    pl = inject_faults(place_network(layers), f, w_max=w_max)
    ref = pl.extract_params()
    x, tgt = _x(dims), _x(dims, seed=3)[:, :dims[-1]]
    for step, atol in enumerate((1e-6, 1e-5)):
        ref, ref_err = xb.paper_backprop_step(ref, x, tgt, PAPER_SPEC,
                                              lr=0.5)
        ref = reapply(place_network(ref, pl.nmap), f,
                      w_max=w_max).extract_params()
        err = chip.train_step(x, tgt, lr=0.5)
        np.testing.assert_allclose(np.asarray(err), np.asarray(ref_err),
                                   atol=atol)
    assert len(calls) == 2
    for a, b in zip(chip.layers(), ref):
        for k in ("g_plus", "g_minus"):
            np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                       atol=1e-5)
    for st in chip.placement.stages:
        for g, salt in ((st.g_plus, 2 * st.index),
                        (st.g_minus, 2 * st.index + 1)):
            on, off = (np.asarray(m) for m in f.masks(g.shape, salt=salt))
            assert off.any() and on.any()
            assert not np.asarray(g)[off].any()
            assert (np.asarray(g)[on] == w_max).all()
    # the next call runs on the masked stacks, not the pre-mask envelope
    st = chip._get_stacks()
    for s, stage in enumerate(chip.placement.stages):
        T = stage.g_plus.shape[0]
        np.testing.assert_array_equal(np.asarray(st.g_plus[s, :T]),
                                      np.asarray(stage.g_plus))


def test_forced_kernel_body_matches_reference_math(monkeypatch):
    """REPRO_SIM_FORCE_KERNELS=1 swaps the compiled scan body onto the
    fused Pallas megakernel (the TPU path) — numerics must match the
    reference-math body.  Keeps the kernel-in-scan integration covered on
    CPU, where the default body is the jnp reference."""
    dims = [41, 15, 41]
    layers = _layers(dims)
    x, tgt = _x(dims), _x(dims, seed=3)[:, :dims[-1]]
    chip_ref = VirtualChip([dict(p) for p in layers], PAPER_SPEC)
    ref_used_kernels = chip_ref._cfg.use_kernels
    y_ref = chip_ref.infer(x)
    e_ref = chip_ref.train_step(x, tgt, lr=0.1)
    monkeypatch.setenv("REPRO_SIM_FORCE_KERNELS", "1")
    chip_k = VirtualChip([dict(p) for p in layers], PAPER_SPEC)
    assert chip_k._cfg.use_kernels
    if ref_used_kernels:
        pytest.skip("backend already runs the kernel body by default")
    np.testing.assert_allclose(np.asarray(chip_k.infer(x)),
                               np.asarray(y_ref), atol=1e-6)
    e_k = chip_k.train_step(x, tgt, lr=0.1)
    np.testing.assert_allclose(np.asarray(e_k), np.asarray(e_ref),
                               atol=1e-6)
    for a, b in zip(chip_k.layers(), chip_ref.layers()):
        np.testing.assert_allclose(np.asarray(a["g_plus"]),
                                   np.asarray(b["g_plus"]), atol=1e-6)


# ---------------------------------------------------------------------------
# Exactly one compilation per (topology, batch) shape
# ---------------------------------------------------------------------------

def test_one_compile_per_topology_and_batch():
    dims = [41, 15, 41]
    x, tgt = _x(dims, n=4), _x(dims, n=4)
    chips = [VirtualChip(_layers(dims, seed=s), PAPER_SPEC)
             for s in range(2)]
    for chip in chips:
        for _ in range(3):
            chip.train_step(x, tgt, lr=0.1)
            chip.infer(x)
    # the slice-wise entry points of the pipeline fabric: wave, then the
    # bwd/update phases on the wave's per-stage views
    for chip in chips:
        for _ in range(3):
            acts, dps, out = chip.forward_wave(x, train=True)
            chip.backward_update(acts, dps, tgt - out, 0.1)
    counts = csim.trace_counts()
    st = chips[0]._get_stacks()
    cfg = csim.chip_config(st, PAPER_SPEC)
    # the per-stage cores and widths the programs cut their outputs to
    # are static config: one topology, one config
    assert (cfg.Ts, cfg.fan_in, cfg.fan_out) == (st.Ts, (41, 15), (15, 41))
    assert cfg == csim.chip_config(chips[1]._get_stacks(), PAPER_SPEC)
    key_train = ("chip_train", cfg, (4, 41), None)
    key_infer = ("chip_infer", cfg, (4, 41))
    assert counts[key_train] == 1, counts
    assert counts[key_infer] == 1, counts
    assert counts[("chip_forward", cfg, (4, 41))] == 1, counts
    assert counts[("chip_backward", cfg, (4, st.N_pad), None)] == 1, counts
    assert counts[("pad_wave", cfg, (4, 41))] == 1, counts
    # an lr schedule reuses the SAME executable (lr_eff is traced) ...
    chips[0].train_step(x, tgt, lr=0.37)
    assert csim.trace_counts()[key_train] == 1
    # ... while a new batch shape is a new program — exactly one trace
    chips[0].train_step(_x(dims, n=2), tgt[:2], lr=0.1)
    counts = csim.trace_counts()
    assert counts[("chip_train", cfg, (2, 41), None)] == 1, counts
    assert counts[key_train] == 1, counts


# ---------------------------------------------------------------------------
# Buffer donation: the compiled step updates conductances in place
# ---------------------------------------------------------------------------

def test_train_step_lowering_declares_donation():
    dims = [41, 15, 41]
    chip = VirtualChip(_layers(dims), PAPER_SPEC)
    st = chip._get_stacks()
    lowered = csim.chip_train.lower(
        st.g_plus, st.g_minus, _x(dims, n=2),
        _x(dims, n=2)[:, :dims[-1]], st.index_pytree(), chip._cfg,
        lr_eff=0.05)
    txt = lowered.as_text()
    assert "tf.aliasing_output" in txt or "donated" in txt, \
        "compiled train_step does not declare input-output aliasing"


def test_train_step_donates_conductance_stacks_in_place():
    dims = [41, 15, 41]
    chip = VirtualChip(_layers(dims), PAPER_SPEC)
    x, tgt = _x(dims, n=4), _x(dims, n=4)
    chip.train_step(x, tgt, lr=0.1)      # warm up / compile
    st = chip._get_stacks()
    try:
        before = {st.g_plus.unsafe_buffer_pointer(),
                  st.g_minus.unsafe_buffer_pointer()}
    except (AttributeError, NotImplementedError):
        pytest.skip("unsafe_buffer_pointer unavailable on this backend")
    chip.train_step(x, tgt, lr=0.1)
    st = chip._get_stacks()
    after = {st.g_plus.unsafe_buffer_pointer(),
             st.g_minus.unsafe_buffer_pointer()}
    assert after == before, "donated stacks were copied, not reused"


def test_repeated_steps_are_allocation_stable():
    dims = [41, 15, 41]
    chip = VirtualChip(_layers(dims), PAPER_SPEC)
    x, tgt = _x(dims, n=4), _x(dims, n=4)
    for _ in range(3):                   # warm up compile + caches
        chip.train_step(x, tgt, lr=0.1)
    chip.layers()                        # materialize the read-back path
    base = len(jax.live_arrays())
    for _ in range(5):
        chip.train_step(x, tgt, lr=0.1)
    assert len(jax.live_arrays()) <= base + 2, \
        "compiled training leaks device buffers per step"


# ---------------------------------------------------------------------------
# Writeback: the training programs return the per-stage layout
# ---------------------------------------------------------------------------

RAGGED = dict(dims=[100, 40, 30, 10], rows=40, cols=10)  # T = 12, 6, 1


def _capture(monkeypatch, name):
    """Wrap ``csim.<name>``: every call's outputs, in call order."""
    calls, orig = [], getattr(csim, name)

    def wrapped(*args, **kw):
        out = orig(*args, **kw)
        calls.append(out)
        return out

    monkeypatch.setattr(csim, name, wrapped)
    return calls


def _build(kind):
    from repro.sim.cluster import ChipFarm
    from repro.sim.fabric import ChipPipeline
    geo = dict(rows=RAGGED["rows"], cols=RAGGED["cols"])
    layers = _layers(RAGGED["dims"])
    if kind == "chip":
        return VirtualChip(layers, PAPER_SPEC, **geo)
    if kind == "pipeline":
        return ChipPipeline(layers, PAPER_SPEC, n_chips=2, **geo)
    return ChipFarm(layers, PAPER_SPEC, n_chips=2, **geo)


def _slices(obj, kind):
    """(holders, envelope) per slice of ``obj``: what the object keeps per
    stage after a step, and the padded envelope it donates next."""
    if kind == "farm":
        yield list(zip(obj._gp, obj._gm)), (obj._fgp, obj._fgm)
        return
    for chip in (obj.chips if kind == "pipeline" else [obj]):
        st = chip._stacks
        yield ([(s.g_plus, s.g_minus) for s in chip.placement.stages],
               (st.g_plus, st.g_minus))


def _cut(held, e, s):
    """Stage ``s`` of envelope ``e`` cut to the cores ``held`` has."""
    return np.asarray(e[s][..., :held.shape[-3], :, :])


@pytest.mark.parametrize("kind", ["chip", "pipeline", "farm"])
def test_writeback_keeps_the_programs_own_views(monkeypatch, kind):
    """After a compiled step each stage holds the very arrays the program
    returned, bitwise the envelope cut to the stage's cores, and the
    per-layer conductances read the same as from that cut."""
    import dataclasses
    name = "chip_backward" if kind == "pipeline" else "chip_train"
    outputs = _capture(monkeypatch, name)
    obj = _build(kind)
    x = _x(RAGGED["dims"], n=4)
    tgt = _x(RAGGED["dims"], n=4, seed=3)[:, :RAGGED["dims"][-1]]
    obj.train_step(x, tgt, 0.1)
    seen = 0
    for holders, env in _slices(obj, kind):
        out, = [o for o in outputs if o[0] is env[0]]
        assert isinstance(out, csim.Stepped)
        for s, held in enumerate(holders):
            for g, e, v in zip(held, env, out.views):
                assert g is v[s]
                np.testing.assert_array_equal(np.asarray(g), _cut(g, e, s))
                seen += 1
    assert seen == 2 * len(RAGGED["dims"][1:])
    # the per-layer read-back equals the one from the envelope's slices
    pl = obj.placement
    if kind == "farm":
        stages = [dataclasses.replace(
            st, g_plus=obj._fgp[s, 0, :st.row_tiles * st.col_tiles],
            g_minus=obj._fgm[s, 0, :st.row_tiles * st.col_tiles])
            for s, st in enumerate(pl.stages)]
    else:
        chips = obj.chips if kind == "pipeline" else [obj]
        stages = [dataclasses.replace(
            st, g_plus=c._stacks.g_plus[s, :st.row_tiles * st.col_tiles],
            g_minus=c._stacks.g_minus[s, :st.row_tiles * st.col_tiles])
            for c in chips for s, st in enumerate(c.placement.stages)]
    want = dataclasses.replace(pl, stages=stages).extract_params()
    for a, b in zip(obj.layers(), want):
        for k in ("g_plus", "g_minus"):
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]))


@pytest.mark.parametrize("kind", ["chip", "pipeline", "farm"])
def test_writeback_keeps_a_rebuilt_results_stacks(monkeypatch, kind):
    """A result rebuilt around other stacks (a plain tuple, as a wrapper of
    the program returns) is what the object keeps: each stage holds those
    stacks cut to its cores, not the views the program cut from its own."""
    name = "chip_backward" if kind == "pipeline" else "chip_train"
    orig, made = getattr(csim, name), []

    def halved(*args, **kw):
        out = orig(*args, **kw)
        made.append(((out[0] * 0.5, out[1] * 0.5), out.views))
        return made[-1][0] + out[2:]

    monkeypatch.setattr(csim, name, halved)
    obj = _build(kind)
    x = _x(RAGGED["dims"], n=4)
    tgt = _x(RAGGED["dims"], n=4, seed=3)[:, :RAGGED["dims"][-1]]
    obj.train_step(x, tgt, 0.1)
    seen = 0
    for holders, env in _slices(obj, kind):
        (rebuilt, views), = [m for m in made if m[0][0] is env[0]]
        for s, held in enumerate(holders):
            for g, e, v in zip(held, env, views):
                np.testing.assert_array_equal(np.asarray(g), _cut(g, e, s))
                assert np.any(np.asarray(v[s]))
                np.testing.assert_array_equal(2 * np.asarray(g),
                                              np.asarray(v[s]))
                seen += 1
    assert seen == 2 * len(RAGGED["dims"][1:])


def _eager_envelope(acts, dps, delta, st):
    """The padded wave built op by op outside any program: zeros, then
    one ``.at[].set`` per stage and operand."""
    M = delta.shape[0]
    acts_p = jnp.zeros((st.S, M, st.L), jnp.float32)
    dps_p = jnp.zeros((st.S, M, st.N_pad), jnp.float32)
    for s in range(st.S):
        acts_p = acts_p.at[s, :, 1:st.fan_in[s] + 1].set(acts[s])
        dps_p = dps_p.at[s, :, :st.fan_out[s]].set(dps[s])
    delta_p = jnp.zeros((M, st.N_pad), jnp.float32)
    return acts_p, dps_p, delta_p.at[:, :delta.shape[1]].set(delta)


def test_pad_wave_equals_the_eager_envelope_bitwise():
    """Each slice of a two-chip pipeline with ragged stages pads its wave
    in one program into the very envelope an op-by-op build makes: payload
    lanes, the zero bias slot and every zero lane beyond a stage's
    widths."""
    pipe = _build("pipeline")
    x = _x(RAGGED["dims"], n=4)
    h = x
    for k, chip in enumerate(pipe.chips):
        acts, dps, h = chip.forward_wave(h, count=False,
                                         quantize_tail=k == 0)
        delta = _x([h.shape[1]], n=4, seed=5 + k)
        st = chip._get_stacks()
        got = csim.pad_wave(acts, dps, delta, chip._cfg)
        want = _eager_envelope(acts, dps, delta, st)
        for g, w in zip(got, want):
            assert g.shape == w.shape and g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        acts_p, dps_p, delta_p = (np.asarray(a) for a in got)
        assert not np.any(acts_p[:, :, 0])             # the bias slot
        for s in range(st.S):
            assert not np.any(acts_p[s, :, st.fan_in[s] + 1:])
            assert not np.any(dps_p[s, :, st.fan_out[s]:])
        assert not np.any(delta_p[:, delta.shape[1]:])
        assert np.any(acts_p) and np.any(dps_p) and np.any(delta_p)
    # the second slice pads stages of 6 and 1 cores into one envelope
    assert [c._get_stacks().Ts for c in pipe.chips] == [(12,), (6, 1)]


def _phase_launches(log_dir, phase="repro.prepare") -> int:
    """Program launches (``PjitFunction``) and transfers (``DevicePut``)
    the host dispatched inside ``phase`` spans of a profiler trace, an
    event nested in another counted event counted once."""
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    n = 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            evs = [(ev.name, int(ev.start_ns),
                    int(ev.start_ns) + int(ev.duration_ns))
                   for ev in line.events]
            spans = [(a, b) for name, a, b in evs if name == phase]
            hits = sorted(((a, -b) for name, a, b in evs
                           if name.startswith(("PjitFunction", "DevicePut"))
                           and any(p <= a and b <= q for p, q in spans)))
            end = None
            for a, neg_b in hits:
                if end is None or a >= end:      # not inside the last one
                    n, end = n + 1, -neg_b
    return n


def _traced(log_dir, calls: int, fn) -> None:
    """``calls`` blocking calls of ``fn`` under the profiler, host events
    kept."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        for _ in range(calls):
            jax.block_until_ready(fn())


def test_pipeline_train_prepare_launches_one_program_per_chip(tmp_path):
    """Once its shapes are warm, a pipeline training step builds its
    operands with at most one program per modelled chip (the padded
    wave): nothing eager, no transfer of an unchanged scalar."""
    pipe = _build("pipeline")
    x = _x(RAGGED["dims"], n=4)
    tgt = _x(RAGGED["dims"], n=4, seed=3)[:, :RAGGED["dims"][-1]]
    jax.block_until_ready(pipe.train_step(x, tgt, 0.1))   # compile, place
    steps = 2
    _traced(tmp_path, steps, lambda: pipe.train_step(x, tgt, 0.1))
    n = _phase_launches(tmp_path)
    assert 0 < n <= steps * pipe.n_chips, n


@pytest.mark.parametrize("kind", ["pipeline", "farm"])
def test_serve_session_launches_do_not_grow_with_requests(tmp_path, kind):
    """A warm serving session moves its requests to the device and its
    answers back in a fixed number of programs, whatever the number of
    requests: at most three in each of prepare and writeback."""
    obj = _build(kind)
    sessions, per_q = 2, {}
    for Q in (4, 16):
        x = _x(RAGGED["dims"], n=Q)
        jax.block_until_ready(obj.serve(x)[0])            # compile, place
        log = tmp_path / str(Q)
        _traced(log, sessions, lambda: obj.serve(x)[0])
        per_q[Q] = [_phase_launches(log, phase)
                    for phase in ("repro.prepare", "repro.writeback")]
    assert per_q[4] == per_q[16], per_q
    assert all(0 < n <= 3 * sessions for n in per_q[4]), per_q


@pytest.mark.parametrize("m", [1, 3])
@pytest.mark.parametrize("kind", ["pipeline", "farm"])
def test_serve_session_answers_are_the_scan_rows(monkeypatch, kind, m):
    """Five requests (padding rows and idle lanes in the slab): each
    answer is bitwise its request's row of the `serve_scan` output, in
    the request's microbatch shape, and the session's answers are their
    concatenation; through `serve` (one-row requests) as well."""
    from repro.runtime.serve_loop import RequestQueue
    from repro.sim.cluster import FarmServer
    from repro.sim.fabric import PipelineServer
    scans = _capture(monkeypatch, "serve_scan")
    obj = _build(kind)
    server = (PipelineServer if kind == "pipeline" else FarmServer)(obj)
    Q, out_dim = 5, RAGGED["dims"][-1]
    xs = [_x(RAGGED["dims"], n=m, seed=20 + i) for i in range(Q)]
    queue = RequestQueue(xs)
    stats, answers = server.session(queue)
    assert stats["retired"] == Q * m and queue.drained
    scan = np.asarray(scans[-1])
    got = queue.results()
    for i, a in enumerate(got):
        assert a.shape == (m, out_dim)
        np.testing.assert_array_equal(np.asarray(a), scan[i])
    np.testing.assert_array_equal(np.asarray(answers),
                                  np.concatenate([np.asarray(a)
                                                  for a in got]))
    if m == 1:
        out, _ = obj.serve(jnp.concatenate(xs))
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(scans[-1])[:Q, 0])


def _server_on(kind):
    """(owner, server, lanes C): a two-chip pipeline (one lane), or a farm
    of three replicas whose replica 1 holds other conductances than the
    rest, so that an answer from the wrong lane reads differently."""
    from repro.sim.cluster import ChipFarm, FarmServer
    from repro.sim.fabric import PipelineServer
    if kind == "pipeline":
        obj = _build("pipeline")
        return obj, PipelineServer(obj), 1
    geo = dict(rows=RAGGED["rows"], cols=RAGGED["cols"])
    obj = ChipFarm(_layers(RAGGED["dims"]), PAPER_SPEC, n_chips=3, **geo)
    obj._gp = [g.at[1].set(g[1][..., ::-1]) for g in obj._gp]
    obj._gm = [g.at[1].set(0.5 * g[1]) for g in obj._gm]
    obj.version += 1
    return obj, FarmServer(obj), 3


@pytest.mark.parametrize("m", [1, 3])
@pytest.mark.parametrize("kind", ["pipeline", "farm"])
def test_serve_session_answers_are_chip_infer_on_their_lane(kind, m):
    """Five requests (a ragged last lane on the farm): each answer is
    bitwise `chip_infer` of its request's rows over the same stacks, in
    lane ``r % C`` — the session is the recognition wave, on the lane the
    schedule names.  The reference batch is built here from the schedule:
    lane ``c`` holds its requests' rows in order, zero rows after, as
    deep as the session's power-of-two lane depth (the contraction's
    rounding may follow the batch's shape)."""
    from repro.runtime.serve_loop import RequestQueue
    obj, server, C = _server_on(kind)
    Q, D = 5, RAGGED["dims"][0]
    xs = [_x(RAGGED["dims"], n=m, seed=40 + i) for i in range(Q)]
    queue = RequestQueue(xs)
    server.session(queue)
    if kind == "pipeline":
        st = obj._get_serve_stacks()
        gp, gm = st.g_plus, st.g_minus
    else:
        st, gp, gm, _ = obj._get_stacks()
        assert not np.array_equal(np.asarray(gp[:, 1]), np.asarray(gp[:, 0]))
    q_max = -(-Q // C)
    depth = 1 << (q_max - 1).bit_length()
    lanes = np.zeros((C, depth * m, D), np.float32)
    for r, x in enumerate(xs):
        lanes[r % C, r // C * m:(r // C + 1) * m] = np.asarray(x)
    cfg = csim.chip_config(st, PAPER_SPEC)
    want, _ = csim.chip_infer(gp, gm, jnp.asarray(lanes), st.index_pytree(),
                              cfg)
    # every lane's rows moved one lane on: where lane 1 takes part, they
    # read other conductances, so a wrong lane would give another answer
    moved, _ = csim.chip_infer(gp, gm, jnp.asarray(np.roll(lanes, 1, 0)),
                               st.index_pytree(), cfg)
    want, moved = np.asarray(want), np.asarray(moved)
    for r, a in enumerate(queue.results()):
        rows = slice(r // C * m, (r // C + 1) * m)
        np.testing.assert_array_equal(np.asarray(a), want[r % C, rows])
        if 1 in (r % C, (r + 1) % C) and C > 1:
            assert not np.array_equal(np.asarray(a),
                                      moved[(r + 1) % C, rows])


def test_serve_sessions_reuse_one_compile_per_lane_depth():
    """Sessions of 3 and 64 requests, twice each, and one of 4 (the same
    power-of-two lane depth as 3): one `serve_scan` compile per lane
    depth, none per session."""
    from repro.sim.fabric import ChipPipeline
    dims = [29, 23, 13, 7]         # a topology no other test compiles
    pipe = ChipPipeline(_layers(dims, seed=5), PAPER_SPEC, n_chips=2,
                        rows=20, cols=10)
    for Q in (3, 64, 3, 4, 64):
        pipe.serve(_x(dims, n=Q, seed=Q))
    st = pipe._get_serve_stacks()
    cfg = csim.chip_config(st, PAPER_SPEC)
    keys = {k: n for k, n in csim.trace_counts().items()
            if k[:2] == ("serve_scan", cfg)}
    assert keys == {("serve_scan", cfg, (4, 1, dims[0]),
                     st.g_plus.shape): 1,
                    ("serve_scan", cfg, (64, 1, dims[0]),
                     st.g_plus.shape): 1}, keys


@pytest.mark.parametrize("kind", ["chip", "farm"])
def test_views_outlive_the_donated_envelope(monkeypatch, kind):
    """Over three donated steps the envelope keeps its buffer, and the
    views step 1 returned stay readable after step 2 donates it."""
    outputs = _capture(monkeypatch, "chip_train")
    obj = _build(kind)
    x = _x(RAGGED["dims"], n=4)
    tgt = _x(RAGGED["dims"], n=4, seed=3)[:, :RAGGED["dims"][-1]]
    ptrs = []
    for step in range(3):
        obj.train_step(x, tgt, 0.1)
        if step == 0:
            first = [np.asarray(v) for v in sum(outputs[0].views, ())]
        try:
            ptrs.append(outputs[-1][0].unsafe_buffer_pointer())
        except (AttributeError, NotImplementedError):
            pytest.skip("unsafe_buffer_pointer unavailable on this backend")
    assert len(set(ptrs)) == 1, "donated envelope was copied, not reused"
    assert outputs[0][0].is_deleted()
    for v, host in zip(sum(outputs[0].views, ()), first):
        assert not v.is_deleted()
        np.testing.assert_array_equal(np.asarray(v), host)


# ---------------------------------------------------------------------------
# Bounded caches + autotune persistence
# ---------------------------------------------------------------------------

def test_pad_cache_is_bounded_lru():
    from repro.kernels.ops import _PAD_CACHE, _PAD_CACHE_MAX, _cached_pad
    _PAD_CACHE.clear()
    arrays = [jnp.ones((3 + i, 5)) for i in range(_PAD_CACHE_MAX + 8)]
    for a in arrays:
        _cached_pad(a, (64, 8))
    assert len(_PAD_CACHE) == _PAD_CACHE_MAX
    # a hit refreshes recency: the refreshed entry survives new inserts
    kept = arrays[-_PAD_CACHE_MAX]
    _cached_pad(kept, (64, 8))
    for a in [jnp.ones((100 + i, 5)) for i in range(_PAD_CACHE_MAX - 1)]:
        _cached_pad(a, (256, 8))
    assert any(v[0] is kept for v in _PAD_CACHE.values())


def test_block_cache_is_bounded_lru():
    from repro.kernels import ops
    saved = dict(ops._BLOCK_CACHE)
    ops._BLOCK_CACHE.clear()
    try:
        for i in range(ops._BLOCK_CACHE_MAX + 50):
            ops.block_config("evict_test", 8, 16 + i, 8)
        assert len(ops._BLOCK_CACHE) == ops._BLOCK_CACHE_MAX
        assert ("evict_test", 8, 16, 8) not in ops._BLOCK_CACHE
    finally:
        ops._BLOCK_CACHE.clear()
        ops._BLOCK_CACHE.update(saved)


def test_stacked_autotune_key_includes_fold_and_persists(tmp_path,
                                                         monkeypatch):
    from repro.kernels import ops
    import json

    table = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_TABLE", str(table))
    # persistence belongs to the opt-in tuning path only
    monkeypatch.setenv("REPRO_XBAR_AUTOTUNE", "1")
    saved = dict(ops._BLOCK_CACHE)
    saved_tuned = set(ops._TUNED_KEYS)
    ops._BLOCK_CACHE.clear()
    ops._TUNED_KEYS.clear()
    try:
        timed = []

        def time_fn(bm, bk, bn):
            timed.append((bm, bk, bn))

        # one timing pass per (op, fold, shape); a second call — and a
        # call with another shape hitting the same fold — must not re-time
        b1 = ops.block_config("fwd_stacked", 4, 41, 15, fold=8,
                              autotune=True, time_fn=time_fn)
        n_timed = len(timed)
        assert n_timed > 0
        assert ops.block_config("fwd_stacked", 4, 41, 15, fold=8,
                                autotune=True, time_fn=time_fn) == b1
        assert len(timed) == n_timed, "re-timed a cached stacked shape"
        # a different farm size is a different fold -> its own entry
        ops.block_config("fwd_stacked", 4, 41, 15, fold=16,
                         autotune=True, time_fn=time_fn)
        assert len(timed) == 2 * n_timed
        assert ("fwd_stacked", 8, 4, 41, 15) in ops._BLOCK_CACHE
        assert ("fwd_stacked", 16, 4, 41, 15) in ops._BLOCK_CACHE
        # an untuned default (no timing pass) is cached for dispatch but
        # NEVER persisted — a persisted default would read as "already
        # tuned" on reload and suppress the timing pass forever ...
        ops.block_config("fwd_stacked", 9, 41, 15, fold=8, autotune=False)
        ops.save_autotune_table()
        assert "fwd_stacked|8|9|41|15" not in json.load(open(table))
        # ... and a later real timing opportunity upgrades it in place
        ops.block_config("fwd_stacked", 9, 41, 15, fold=8, autotune=True,
                         time_fn=time_fn)
        assert ("fwd_stacked", 8, 9, 41, 15) in ops._TUNED_KEYS
        # persistence round-trip
        assert table.exists()
        ops._BLOCK_CACHE.clear()
        assert ops.load_autotune_table() >= 2
        assert ops._BLOCK_CACHE[("fwd_stacked", 8, 4, 41, 15)] == b1
    finally:
        ops._BLOCK_CACHE.clear()
        ops._BLOCK_CACHE.update(saved)
        ops._TUNED_KEYS.clear()
        ops._TUNED_KEYS.update(saved_tuned)


# ---------------------------------------------------------------------------
# StageStacks padding invariance (the §8 bitwise contract)
# ---------------------------------------------------------------------------

def test_stage_stacks_layout_shapes():
    dims = hw.PAPER_NETWORKS["mnist_class"]
    pl = place_network(_layers(dims))
    st = build_stage_stacks(pl)
    assert st.g_plus.shape == (st.S, st.T_max, st.rows, st.cols)
    assert st.in_idx.shape == (st.S, st.T_max, st.rows)
    assert st.N_pad >= max(st.fan_in) and st.N_pad >= max(st.fan_out)
    assert st.L == 1 + st.N_pad
    assert st.out_dim == dims[-1]
    # round trip: the padded stacks reproduce the placed conductances
    for s, stage in enumerate(pl.stages):
        T = stage.row_tiles * stage.col_tiles
        np.testing.assert_array_equal(np.asarray(st.g_plus[s, :T]),
                                      np.asarray(stage.g_plus))
        if T < st.T_max:
            assert float(jnp.abs(st.g_plus[s, T:]).max()) == 0.0


def test_pipeline_slice_envelope_is_bitwise_invisible():
    """The same stage computed inside a small slice envelope and inside
    the full-network envelope must agree BITWISE — the invariance the
    pipeline fabric's slice-vs-serial pins rest on."""
    dims = hw.PAPER_NETWORKS["mnist_class"]
    layers = _layers(dims)
    x = _x(dims, n=3)
    full = VirtualChip([dict(p) for p in layers], PAPER_SPEC)
    from repro.sim.fabric import ChipPipeline
    pipe = ChipPipeline([dict(p) for p in layers], PAPER_SPEC, n_chips=3)
    np.testing.assert_array_equal(np.asarray(pipe.infer(x)),
                                  np.asarray(full.infer(x)))
