"""Compiled whole-step execution (ISSUE 5 / DESIGN.md §8).

Four contracts:

  * the fused training megakernel `crossbar_train_stacked` equals the
    four-call sequence (fwd + bwd + dw + pulse) BITWISE over a sweep of
    shapes and ragged zero-padded core stacks, including the 8-bit
    sign-magnitude error path;
  * the compiled chip/farm/serve paths equal the eager reference path
    (``REPRO_SIM_COMPILED=0``) numerically, with IDENTICAL counters;
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


class _eager_sim:
    """Context manager: force the eager per-stage reference path."""

    def __enter__(self):
        os.environ["REPRO_SIM_COMPILED"] = "0"

    def __exit__(self, *a):
        os.environ.pop("REPRO_SIM_COMPILED", None)


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
# Compiled path == eager reference path (chip, farm, serving)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dims", [[41, 15, 41],
                                  hw.PAPER_NETWORKS["mnist_class"]])
def test_compiled_chip_matches_eager_reference(dims):
    layers = _layers(dims)
    x, tgt = _x(dims), _x(dims, seed=3)[:, :dims[-1]]
    chip_c = VirtualChip([dict(p) for p in layers], PAPER_SPEC)
    with _eager_sim():
        chip_e = VirtualChip([dict(p) for p in layers], PAPER_SPEC)
        ye = chip_e.infer(x)
        for step in range(2):
            ee = chip_e.train_step(x, tgt, lr=0.2)
    yc = chip_c.infer(x)
    for step in range(2):
        ec = chip_c.train_step(x, tgt, lr=0.2)
    np.testing.assert_allclose(np.asarray(yc), np.asarray(ye), atol=1e-6)
    np.testing.assert_allclose(np.asarray(ec), np.asarray(ee), atol=1e-6)
    for a, b in zip(chip_c.layers(), chip_e.layers()):
        np.testing.assert_allclose(np.asarray(a["g_plus"]),
                                   np.asarray(b["g_plus"]), atol=1e-6)
    # accounting is schedule-derived, so it must be EXACTLY equal
    for attr in ("infer_counters", "train_counters"):
        cc, ce = getattr(chip_c, attr), getattr(chip_e, attr)
        assert cc.slots == ce.slots
        assert cc.core_steps == ce.core_steps
        assert cc.samples == ce.samples and cc.io_bits == ce.io_bits
        assert cc.noc.routed_outputs == ce.noc.routed_outputs
        assert cc.noc.max_link_cycles == ce.noc.max_link_cycles


def test_compiled_farm_serve_matches_eager_reference():
    dims = [41, 15, 41]
    x = _x(dims, n=7, seed=5)
    farm_c = build_farm("kdd_anomaly", 2, seed=0)
    out_c, stats_c = farm_c.serve(x)
    with _eager_sim():
        farm_e = build_farm("kdd_anomaly", 2, seed=0)
        out_e, stats_e = farm_e.serve(x)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_e),
                               atol=1e-6)
    assert stats_c == stats_e
    for cc, ce in zip(farm_c.chip_infer, farm_e.chip_infer):
        assert cc.slots == ce.slots and cc.samples == ce.samples
        assert cc.core_steps == ce.core_steps and cc.io_bits == ce.io_bits
    assert farm_c.serve_full_beats == farm_e.serve_full_beats
    assert farm_c.serve_link.sample_bits == farm_e.serve_link.sample_bits


def test_compiled_serve_keeps_cross_session_microbatch_contract():
    """The eager server pins one request microbatch per server lifetime;
    the compiled session path must enforce the same contract (a second
    session with a different microbatch falls back to the eager path,
    which raises the documented error)."""
    from repro.runtime.serve_loop import RequestQueue
    from repro.sim.cluster import FarmServer
    farm = build_farm("kdd_anomaly", 2, seed=0)
    server = FarmServer(farm)
    server.run(RequestQueue([jnp.zeros((2, 41))] * 4))      # m=2 session
    with pytest.raises(ValueError, match="uniform request shapes"):
        server.run(RequestQueue([jnp.zeros((3, 41))] * 4))  # m=3 rejected


def test_compiled_farm_train_matches_eager_reference():
    dims = [41, 15, 41]
    x = _x(dims, n=8, seed=6)
    farm_c = build_farm("kdd_anomaly", 2, seed=0)
    ec = farm_c.train_step(x, x, lr=0.1)
    with _eager_sim():
        farm_e = build_farm("kdd_anomaly", 2, seed=0)
        ee = farm_e.train_step(x, x, lr=0.1)
    np.testing.assert_allclose(np.asarray(ec), np.asarray(ee), atol=1e-6)
    for a, b in zip(farm_c.layers(), farm_e.layers()):
        np.testing.assert_allclose(np.asarray(a["g_plus"]),
                                   np.asarray(b["g_plus"]), atol=1e-6)
    assert farm_c.replicas_in_sync()
    for cc, ce in zip(farm_c.chip_train, farm_e.chip_train):
        assert cc.slots == ce.slots and cc.core_steps == ce.core_steps
    assert (farm_c.train_link.reconcile_bits
            == farm_e.train_link.reconcile_bits)


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
    counts = csim.trace_counts()
    cfg = csim.chip_config(chips[0]._get_stacks(), PAPER_SPEC)
    key_train = ("chip_train", cfg, (4, 41), None)
    key_infer = ("chip_infer", cfg, (4, 41))
    assert counts[key_train] == 1, counts
    assert counts[key_infer] == 1, counts
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
