"""Chip farm (repro.sim.cluster): data-parallel training equals the serial
chip, served outputs equal the reference forward, and the farm-level
accounting cross-validates against both the summed per-chip counters and
the analytic `hw_model.farm_cost` (ISSUE 3 acceptance criteria).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_apps import PAPER_SPEC
from repro.core import crossbar as xb, hw_model as hw
from repro.sim import ChipFarm, VirtualChip
from repro.sim.cluster import FarmServer, build_farm, make_farm_mesh
from repro.runtime.serve_loop import RequestQueue

pytestmark = pytest.mark.sim


def _layers(dims, seed=0, spec=PAPER_SPEC):
    key = jax.random.PRNGKey(seed)
    return [xb.init_conductances(jax.random.fold_in(key, i), f, o, spec)
            for i, (f, o) in enumerate(zip(dims, dims[1:]))]


def _x(dims, n=4, seed=9):
    return jax.random.uniform(jax.random.PRNGKey(seed), (n, dims[0]),
                              minval=-0.5, maxval=0.5)


# ---------------------------------------------------------------------------
# Farm == serial chip (the headline acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dims,n_chips", [
    ([41, 15, 41], 2),                              # single-core layers
    (hw.PAPER_NETWORKS["mnist_class"], 2),          # fan-in split + agg
])
def test_farm_train_matches_serial_chip(dims, n_chips):
    """A 2-chip data-parallel farm on a fixed batch matches
    VirtualChip.train_step applied to the same data serially."""
    layers = _layers(dims)
    farm = ChipFarm([dict(p) for p in layers], PAPER_SPEC, n_chips=n_chips)
    chip = VirtualChip([dict(p) for p in layers], PAPER_SPEC)
    x = _x(dims, n=4)
    tgt = jax.random.uniform(jax.random.PRNGKey(4), (4, dims[-1]),
                             minval=-0.5, maxval=0.5)
    ef = farm.train_step(x, tgt, lr=0.1)
    ec = chip.train_step(x, tgt, lr=0.1)
    np.testing.assert_allclose(np.asarray(ef), np.asarray(ec), atol=1e-6)
    for a, b in zip(farm.layers(), chip.layers()):
        for k in ("g_plus", "g_minus"):
            np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                       atol=1e-6)


def test_farm_multi_step_stays_locked_and_in_sync():
    dims = [41, 15, 41]
    layers = _layers(dims, seed=5)
    farm = ChipFarm([dict(p) for p in layers], PAPER_SPEC, n_chips=2)
    chip = VirtualChip([dict(p) for p in layers], PAPER_SPEC)
    for step in range(3):
        x = _x(dims, n=4, seed=20 + step)
        farm.train_step(x, x, lr=0.2)
        chip.train_step(x, x, lr=0.2)
    assert farm.replicas_in_sync()
    for a, b in zip(farm.layers(), chip.layers()):
        np.testing.assert_allclose(np.asarray(a["g_plus"]),
                                   np.asarray(b["g_plus"]), atol=1e-5)


def test_farm_infer_matches_chip():
    dims = [41, 15, 41]
    layers = _layers(dims)
    farm = ChipFarm([dict(p) for p in layers], PAPER_SPEC, n_chips=2)
    chip = VirtualChip([dict(p) for p in layers], PAPER_SPEC)
    x = _x(dims, n=6)
    np.testing.assert_allclose(np.asarray(farm.infer(x)),
                               np.asarray(chip.infer(x)), atol=1e-6)


def test_int8_reconcile_keeps_replicas_in_sync():
    """Compressed reconciliation changes the update (bounded error) but
    every replica still applies the SAME pulses — no silent drift."""
    dims = [41, 15, 41]
    layers = _layers(dims)
    farm = ChipFarm([dict(p) for p in layers], PAPER_SPEC, n_chips=2)
    x = _x(dims)
    farm.train_step(x, x, lr=0.3, reconcile="int8")
    assert farm.replicas_in_sync()


def test_batch_must_divide_over_chips():
    farm = ChipFarm(_layers([41, 15, 41]), PAPER_SPEC, n_chips=2)
    with pytest.raises(ValueError):
        farm.train_step(_x([41, 15, 41], n=3), _x([41, 15, 41], n=3), lr=0.1)


# ---------------------------------------------------------------------------
# Serving front-end
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dims", [[41, 15, 41],
                                  hw.PAPER_NETWORKS["mnist_class"]])
def test_served_outputs_equal_mlp_forward(dims):
    layers = _layers(dims)
    farm = ChipFarm([dict(p) for p in layers], PAPER_SPEC, n_chips=2)
    x = _x(dims, n=6)
    out, stats = farm.serve(x)
    ref = xb.mlp_forward(layers, x, PAPER_SPEC)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    assert stats["retired"] == 6
    assert stats["beat_us"] == pytest.approx(0.77)


def test_serving_preserves_request_order_across_chips():
    """Round-robin routing over chips must not reorder the client-visible
    result stream."""
    dims = [41, 15, 41]
    layers = _layers(dims)
    farm = ChipFarm([dict(p) for p in layers], PAPER_SPEC, n_chips=3)
    chip = VirtualChip([dict(p) for p in layers], PAPER_SPEC)
    x = _x(dims, n=7)          # not divisible by 3: last beat partially idle
    out, _ = farm.serve(x)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(chip.infer(x)), atol=1e-6)


def test_serve_beats_and_throughput_scaling():
    """Q requests over C chips retire in S-1 + Q/C beats; steady-state
    throughput is C samples per beat — monotone in the chip count."""
    dims = [41, 15, 41]
    layers = _layers(dims)
    x = _x(dims, n=8)
    S = len(dims) - 1
    sps = []
    for chips in (1, 2, 4):
        farm = ChipFarm([dict(p) for p in layers], PAPER_SPEC,
                        n_chips=chips)
        _, stats = farm.serve(x)
        assert stats["beats"] == S - 1 + 8 // chips
        sps.append(stats["samples_per_s"])
        assert stats["samples_per_s"] == pytest.approx(
            chips * 1e6 / farm.beat_us)
    assert sps[0] < sps[1] < sps[2]


def test_farm_server_rejects_stale_conductance_snapshot():
    """A FarmServer built before a train_step holds stale stacks; using
    it must fail loudly rather than serve outdated weights."""
    dims = [41, 15, 41]
    farm = ChipFarm(_layers(dims), PAPER_SPEC, n_chips=2)
    server = FarmServer(farm)
    x = _x(dims, n=2)
    farm.train_step(x, x, lr=0.1)
    with pytest.raises(RuntimeError, match="fresh server"):
        server.run(RequestQueue(list(x)))
    out, _ = farm.serve(x)      # a fresh server sees the new weights
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(xb.mlp_forward(farm.layers(), x, PAPER_SPEC)),
        atol=1e-5)


def test_serve_empty_queue_and_shared_placement_validation():
    farm = build_farm("kdd_anomaly", 2, seed=0, share_small_layers=True)
    out, stats = farm.serve(jnp.zeros((0, 41)))
    assert out.shape == (0, 41) and stats["retired"] == 0
    # a shared-placement farm cross-validates against farm_cost built
    # with the SAME share_small_layers setting (report carries it)
    x = _x([41, 15, 41], n=4, seed=3)
    farm.serve(x)
    farm.train_step(x, x, lr=0.1)
    errs = {**farm.report().compare_chip_sum(), **farm.report().compare_hw()}
    assert all(v <= 0.01 for v in errs.values()), errs


def test_farm_server_rejects_ragged_request_batches():
    """The per-beat slab needs one static microbatch shape; a mixed-shape
    queue must fail loudly, not mis-assemble."""
    dims = [41, 15, 41]
    farm = ChipFarm(_layers(dims), PAPER_SPEC, n_chips=1)
    server = FarmServer(farm)
    queue = RequestQueue()
    queue.submit(jnp.zeros((1, 41)))
    queue.submit(jnp.zeros((3, 41)))
    with pytest.raises(ValueError, match="microbatch"):
        server.run(queue)


def test_farm_server_uniform_microbatches_supported():
    """Uniform (m, D) requests serve m samples per slot per beat."""
    dims = [41, 15, 41]
    layers = _layers(dims)
    farm = ChipFarm([dict(p) for p in layers], PAPER_SPEC, n_chips=2)
    server = FarmServer(farm)
    reqs = [_x(dims, n=3, seed=s) for s in (1, 2, 3, 4)]
    queue = RequestQueue(reqs)
    stats = server.run(queue)
    assert stats["retired"] == 12           # 4 requests x 3 samples
    for got, x in zip(queue.results(), reqs):
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(xb.mlp_forward(layers, x, PAPER_SPEC)), atol=1e-5)


def test_farm_server_per_slot_refill():
    """The queue refills each chip's stage-0 slot per beat; a queue larger
    than the farm drains completely and completes every request once."""
    dims = [41, 15, 41]
    farm = ChipFarm(_layers(dims), PAPER_SPEC, n_chips=2)
    server = FarmServer(farm)
    queue = RequestQueue(list(_x(dims, n=5)))
    stats = server.run(queue)
    assert queue.drained and queue.completed == 5
    assert stats["retired"] == 5
    with pytest.raises(ValueError):
        queue.complete(0, None)    # double-completion is an error


# ---------------------------------------------------------------------------
# Farm accounting: measured counters vs chip sums vs analytic model
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("app,chips", [("kdd_anomaly", 2),
                                       ("mnist_class", 2)])
def test_farm_cross_validation_within_1pct(app, chips):
    dims = hw.PAPER_NETWORKS[app]
    farm = build_farm(app, chips, seed=0)
    x = _x(dims, n=2 * chips, seed=1)
    farm.serve(x)
    tgt = jax.random.uniform(jax.random.PRNGKey(5), (2 * chips, dims[-1]),
                             minval=-0.5, maxval=0.5)
    farm.train_step(x, tgt, lr=0.1)
    rep = farm.report()
    errs = {**rep.compare_chip_sum(), **rep.compare_hw()}
    assert {"serve_energy_vs_chips", "train_energy_vs_chips",
            "infer_lockstep", "train_lockstep", "serve_energy",
            "train_energy", "beat", "serve_throughput",
            "host_serve_bits", "train_step_time",
            "reconcile_bits"} <= set(errs)
    for k, v in errs.items():
        assert v <= 0.01, (app, k, v)


def test_ragged_request_count_still_cross_validates():
    """7 requests on 2 chips leave the final beat half idle; capacity is
    measured over full beats only, so the 1% gate still holds."""
    farm = build_farm("kdd_anomaly", 2, seed=0)
    farm.serve(_x([41, 15, 41], n=7, seed=4))
    rep = farm.report()
    errs = {**rep.compare_chip_sum(), **rep.compare_hw()}
    assert "serve_throughput" in errs
    assert all(v <= 0.01 for v in errs.values()), errs
    assert rep.serve_samples_per_s == pytest.approx(2e6 / farm.beat_us)


def test_custom_grid_farm_cross_validates():
    """farm_cost honors a non-default core grid end to end (mapping,
    beat, phase costs), so small-grid farms meet the same contract."""
    dims = [20, 10, 5]
    layers = _layers(dims, seed=3)
    farm = ChipFarm([dict(p) for p in layers], PAPER_SPEC, n_chips=2,
                    rows=16, cols=8, name="small_grid")
    x = _x(dims, n=4, seed=5)
    farm.serve(x)
    farm.train_step(x, jax.random.uniform(jax.random.PRNGKey(6), (4, 5),
                                          minval=-0.5, maxval=0.5), lr=0.1)
    errs = {**farm.report().compare_chip_sum(),
            **farm.report().compare_hw()}
    assert all(v <= 0.01 for v in errs.values()), errs


def test_farm_report_aggregates_per_chip_counters():
    farm = build_farm("kdd_anomaly", 2, seed=0)
    x = _x([41, 15, 41], n=4, seed=2)
    farm.serve(x)
    rep = farm.report()
    assert rep.n_chips == 2 and len(rep.per_chip) == 2
    assert sum(r.infer_samples for r in rep.per_chip) == 4
    assert rep.cores == 2 * farm.placement.n_cores
    # farm energy = per-chip energy + host link, never less than chips alone
    chip_j = sum(r.infer_total_j * r.infer_samples
                 for r in rep.per_chip) / 4
    assert rep.serve_j_per_sample > chip_j


def test_reconcile_traffic_measured_from_stack_sizes():
    farm = build_farm("kdd_anomaly", 2, seed=0)
    x = _x([41, 15, 41], n=2)
    farm.train_step(x, x, lr=0.1)
    rep = farm.report()
    cells = sum(st.g_plus.size for st in farm.placement.stages)
    assert rep.host_reconcile_bits == 2 * 2 * cells * hw.ERR_BITS_LINK


# ---------------------------------------------------------------------------
# Reconciliation collectives
# ---------------------------------------------------------------------------

def test_farm_reduce_sum_modes():
    from repro.dist.collectives import farm_reduce_sum
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 4, 5))
    exact = farm_reduce_sum(x, mode="none")
    np.testing.assert_allclose(np.asarray(exact), np.asarray(x.sum(0)),
                               atol=1e-6)
    coded = farm_reduce_sum(x, mode="int8")
    # bounded code error: per-element within half a step of the full-scale
    scale = float(jnp.max(jnp.abs(x))) / 127
    assert float(jnp.abs(coded - x.sum(0)).max()) <= 3 * 0.5 * scale + 1e-6
    with pytest.raises(ValueError):
        farm_reduce_sum(x, mode="fp4")


def test_int8_reconcile_scales_per_chip():
    """Each chip's contribution is coded against its OWN full-scale: a
    quiet chip's update must survive next to a loud chip's, instead of
    being flushed to zero by a farm-global scale.  Asserted on the quiet
    chip's residual at ITS quantization step — the total would hide the
    flush inside the loud chip's magnitude."""
    from repro.dist.collectives import farm_reduce_sum
    loud = jnp.full((1, 4), 100.0)
    quiet = jnp.full((1, 4), 1e-3)
    out = farm_reduce_sum(jnp.stack([loud, quiet]), mode="int8")
    # per-chip coding leaves ~5e-7 residual; a farm-global scale would
    # flush the whole 1e-3 contribution
    np.testing.assert_allclose(np.asarray(out - loud), np.asarray(quiet),
                               atol=1e-4)


def test_farm_max_is_global_max(subproc):
    from repro.dist.collectives import farm_max
    x = jnp.arange(12.0).reshape(3, 4)
    np.testing.assert_array_equal(np.asarray(farm_max(x)),
                                  np.asarray(x.max(0, keepdims=True)))
    # inside shard_map the same helper is a pmax over the mesh axis
    out = subproc("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.dist.collectives import farm_max
mesh = jax.make_mesh((4,), ("chips",))
x = jnp.arange(8.0).reshape(4, 2)
fn = jax.shard_map(lambda v: farm_max(v, axis_name="chips"),
                   mesh=mesh, in_specs=P("chips"), out_specs=P("chips"),
                   check_vma=False)
y = fn(x)
assert bool((y == x.max(0)).all()), y
print("OK")
""", devices=4)
    assert "OK" in out


def test_int8_reconcile_codes_each_chip_on_a_mesh(subproc):
    """Inside shard_map with two chips on each device, every chip is still
    coded against its own full-scale: a loud and a quiet chip that share a
    device reconcile as they do on one device."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.dist.collectives import farm_reduce_sum
mesh = jax.make_mesh((4,), ("chips",))
scales = jnp.array([100.0, 1e-3, 3.0, 2e-2, 5.0, 1e-4, 7.0, 1.0])
x = jax.random.normal(jax.random.PRNGKey(0), (8, 3, 5)) \
    * scales[:, None, None]
fn = jax.shard_map(lambda v: farm_reduce_sum(v, mode="int8",
                                             axis_name="chips"),
                   mesh=mesh, in_specs=P("chips"), out_specs=P(),
                   check_vma=False)
# equal up to the order of the f32 sum (each device adds its two chips
# before the psum); coding the pair on device 0 with one full-scale would
# move the quiet chip 1 by up to half a step of the loud one's, about 1
want = farm_reduce_sum(x, mode="int8")
np.testing.assert_allclose(np.asarray(fn(x)), np.asarray(want),
                           rtol=1e-6, atol=0)
print("OK")
""", devices=4)
    assert "OK" in out


# ---------------------------------------------------------------------------
# Device-mesh execution (shard_map over the chip axis)
# ---------------------------------------------------------------------------

def test_meshed_farm_matches_single_device(subproc):
    """A farm on a 4-device mesh equals the same farm on one device: the
    compiled programs under ``shard_map`` against the same programs with
    the chip axis as an array axis, for ``infer``, ``train_step`` and
    ``serve`` (outputs within 1e-6, counters exactly equal)."""
    out = subproc("""
import jax, jax.numpy as jnp
from repro.configs.paper_apps import PAPER_SPEC
from repro.core import crossbar as xb
from repro.sim import ChipFarm, compiled as csim
from repro.sim.cluster import make_farm_mesh
key = jax.random.PRNGKey(0)
dims = [41, 15, 41]
L = [xb.init_conductances(jax.random.fold_in(key, i), f, o, PAPER_SPEC)
     for i, (f, o) in enumerate(zip(dims, dims[1:]))]
mesh = make_farm_mesh(4)
assert mesh is not None and mesh.shape["chips"] == 4, mesh
farm = ChipFarm([dict(p) for p in L], PAPER_SPEC, n_chips=4, mesh=mesh)
one = ChipFarm([dict(p) for p in L], PAPER_SPEC, n_chips=4)
# each replica's stacks live on the device that runs its chip
assert len(farm._gp[0].sharding.device_set) == 4

def close(a, b):
    d = float(jnp.abs(a - b).max())
    assert d <= 1e-6, d

x = jax.random.uniform(jax.random.PRNGKey(9), (8, 41),
                       minval=-0.5, maxval=0.5)
close(farm.infer(x), one.infer(x))
close(farm.train_step(x, x, lr=0.1), one.train_step(x, x, lr=0.1))
for a, b in zip(farm.layers(), one.layers()):
    for k in ("g_plus", "g_minus"):
        close(a[k], b[k])
# 10 requests over 4 chips: the last beat leaves two lanes idle
meshes, scan = [], csim.serve_scan
def seen(*a, **kw):
    meshes.append(kw.get("mesh"))
    return scan(*a, **kw)
csim.serve_scan = seen
q = jax.random.uniform(jax.random.PRNGKey(19), (10, 41),
                       minval=-0.5, maxval=0.5)
out, stats = farm.serve(q)
ref, ref_stats = one.serve(q)
assert meshes == [mesh, None], meshes
close(out, ref)
assert stats == ref_stats, (stats, ref_stats)
assert float(jnp.abs(out - xb.mlp_forward(farm.layers(), q,
                                          PAPER_SPEC)).max()) <= 1e-5
for attr in ("chip_infer", "chip_train"):
    for a, b in zip(getattr(farm, attr), getattr(one, attr)):
        assert a.slots == b.slots and a.core_steps == b.core_steps, attr
        assert a.samples == b.samples and a.io_bits == b.io_bits, attr
        assert a.noc.records == b.noc.records, attr
for attr in ("serve_beats", "serve_full_beats", "serve_full_samples",
             "serve_full_requests", "serve_sessions", "train_steps"):
    assert getattr(farm, attr) == getattr(one, attr), attr
assert farm.serve_link.sample_bits == one.serve_link.sample_bits
assert farm.train_link.sample_bits == one.train_link.sample_bits
assert farm.train_link.reconcile_bits == one.train_link.reconcile_bits
print("OK")
""", devices=4)
    assert "OK" in out


MESHED_COMPILED = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs.paper_apps import PAPER_SPEC
from repro.core import crossbar as xb
from repro.sim import ChipFarm, VirtualChip, compiled as csim
from repro.sim.cluster import make_farm_mesh
dims, C, reconcile = {dims}, {chips}, "{reconcile}"
key = jax.random.PRNGKey(0)
L = [xb.init_conductances(jax.random.fold_in(key, i), f, o, PAPER_SPEC)
     for i, (f, o) in enumerate(zip(dims, dims[1:]))]
mesh = make_farm_mesh(C)
assert mesh.shape["chips"] == 4, mesh
farm = ChipFarm([dict(p) for p in L], PAPER_SPEC, n_chips=C, mesh=mesh)
one = ChipFarm([dict(p) for p in L], PAPER_SPEC, n_chips=C)
chip = VirtualChip([dict(p) for p in L], PAPER_SPEC)
assert farm._compiled_active()
csim.reset_trace_counts()
M = 2 * C
for step in range(3):
    x = jax.random.uniform(jax.random.PRNGKey(9 + step), (M, dims[0]),
                           minval=-0.5, maxval=0.5)
    t = jax.random.uniform(jax.random.PRNGKey(19 + step), (M, dims[-1]),
                           minval=-0.5, maxval=0.5)
    ef = farm.train_step(x, t, lr=0.1, reconcile=reconcile)
    # the same program on one device: bitwise equal
    assert np.array_equal(np.asarray(ef), np.asarray(
        one.train_step(x, t, lr=0.1, reconcile=reconcile)))
    if reconcile == "none":
        # the serial chip runs another program (one dw contraction over
        # the whole batch, not a sum of per-chip ones): equal to the
        # float re-association the one-device farm pin allows
        ec = chip.train_step(x, t, lr=0.1)
        assert float(jnp.abs(ef - ec).max()) <= 1e-6
assert farm.replicas_in_sync()
for a, b, c in zip(farm.layers(), one.layers(), chip.layers()):
    for k in ("g_plus", "g_minus"):
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k
        if reconcile == "none":
            assert float(jnp.abs(a[k] - c[k]).max()) <= 1e-6, k
# one trace of the meshed step for the (topology, batch) over 3 steps
keys = [k for k in csim.trace_counts() if k[0] == "chip_train"
        and k[-1] == (("chips", 4),)]
assert len(keys) == 1 and csim.trace_counts()[keys[0]] == 1, keys
# the stacks stay split by chip over the 4 devices after the steps
for g in farm._gp + farm._gm:
    assert len(g.sharding.device_set) == 4
    assert {{s.data.shape[0] for s in g.addressable_shards}} == {{C // 4}}
st, gp, _, _ = farm._get_stacks()
assert {{s.data.shape[1] for s in gp.addressable_shards}} == {{C // 4}}
# the batch stays split by rows, and so does the answer
x = jax.device_put(x, farm._by_chip)
assert farm._rows(x, "x") is x
assert farm._split(x, "x").sharding.spec[0] == "chips"
out = farm.infer(x)
assert out.sharding.spec == jax.sharding.PartitionSpec("chips")
assert np.array_equal(np.asarray(out), np.asarray(one.infer(x)))
if reconcile == "none":
    assert float(jnp.abs(out - chip.infer(x)).max()) <= 1e-6
# serving runs the compiled wave on the mesh, each device on its
# chips' lanes; M + 3 requests leave lanes idle in the last beat
q = jax.random.uniform(jax.random.PRNGKey(29), (M + 3, dims[0]),
                       minval=-0.5, maxval=0.5)
served, stats = farm.serve(q)
want, want_stats = one.serve(q)
assert float(jnp.abs(served - want).max()) <= 1e-6
assert stats == want_stats, (stats, want_stats)
keys = [k for k in csim.trace_counts() if k[0] == "serve_scan"
        and k[-1] == (("chips", 4),)]
assert len(keys) == 1, keys
for a, b in zip(farm.chip_infer, one.chip_infer):
    assert a.slots == b.slots and a.core_steps == b.core_steps
    assert a.samples == b.samples and a.noc.records == b.noc.records
print("OK")
"""


@pytest.mark.parametrize("dims,chips,reconcile", [
    ([41, 15, 41], 4, "none"),
    (hw.PAPER_NETWORKS["mnist_class"], 4, "none"),
    (hw.PAPER_NETWORKS["mnist_class"], 8, "none"),     # 2 chips a device
    ([41, 15, 41], 8, "int8"),     # each chip coded, not each device
])
def test_meshed_farm_runs_the_compiled_step(subproc, dims, chips,
                                            reconcile):
    """A farm on a 4-device mesh runs `train_step` and `infer` through the
    compiled program, sharded: it equals the one-device compiled farm
    bitwise (and the serial chip within re-association), its replicas stay
    in lockstep, it traces once, and stacks and rows stay split.  Its
    serving sessions run the meshed wave and equal the one-device
    farm's, queue length not a multiple of the chips."""
    out = subproc(MESHED_COMPILED.format(dims=list(dims), chips=chips,
                                         reconcile=reconcile), devices=4)
    assert "OK" in out


def test_make_farm_mesh_single_device_is_none():
    # in-process jax has one CPU device: the chip axis stays an array axis
    assert make_farm_mesh(4) is None or jax.local_device_count() > 1


def test_make_farm_mesh_picks_largest_divisor(subproc):
    out = subproc("""
from repro.sim.cluster import make_farm_mesh
assert make_farm_mesh(3).shape["chips"] == 3      # non-power-of-two
assert make_farm_mesh(6).shape["chips"] == 3      # largest divisor <= 4
assert make_farm_mesh(4).shape["chips"] == 4
print("OK", make_farm_mesh(7))
""", devices=4)
    assert "OK None" in out        # 7 chips, 4 devices: no divisor > 1


def test_farm_cost_flags_link_bound_configs():
    """A hypothetical wide-input net saturates the host link: throughput
    stays beat-priced (matching the simulator's idealization) and the
    utilization flag exceeds 1 instead of silently re-pricing."""
    wide = [4000, 100, 10]
    fc = hw.farm_cost("wide", wide, 2)
    assert fc.serve_samples_per_s == pytest.approx(2e6 / fc.beat_us)
    assert fc.host_link_utilization > 1.0
    kdd = hw.farm_cost("kdd_anomaly", [41, 15, 41], 2)
    assert kdd.host_link_utilization < 1.0
