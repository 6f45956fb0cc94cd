"""Golden-value regressions pinning the paper tables and the DESIGN.md §5
cross-validation contract, so refactors of the mapper / hw_model / sim
cannot silently drift (ISSUE 3 satellite).

Values pinned here are either paper numbers (Table III core counts, the
Table IV 0.77 us beat) or the repo's established analytic outputs recorded
at PR 3 time — a change to any of them must be a deliberate, reviewed
decision, not a side effect.
"""
import jax
import pytest

from repro.configs.paper_apps import PAPER_SPEC
from repro.core import crossbar as xb, hw_model as hw
from repro.core.mapping import map_autoencoder_pretraining, map_network
from repro.sim import VirtualChip


def _chip(app, **kw):
    dims = hw.PAPER_NETWORKS[app]
    key = jax.random.PRNGKey(0)
    layers = [xb.init_conductances(jax.random.fold_in(key, i), f, o,
                                   PAPER_SPEC)
              for i, (f, o) in enumerate(zip(dims, dims[1:]))]
    return VirtualChip(layers, PAPER_SPEC, name=app, **kw)


# ---------------------------------------------------------------------------
# Table IV: the 0.77 us pipeline beat, derived from NoC slot counters
# ---------------------------------------------------------------------------

def test_pipeline_beat_is_0_77_us():
    assert hw.pipeline_beat_us() == pytest.approx(0.77, abs=1e-9)


@pytest.mark.sim
def test_chip_beat_from_noc_slot_counters_is_0_77_us():
    """Every Table IV app: 0.27 us crossbar eval + one 100-cycle routing
    slot at 200 MHz, measured from the chip's own NoC slot counters."""
    for app in hw.PAPER_TABLE_IV:
        chip = _chip(app)
        assert chip.beat_us == pytest.approx(0.77, abs=1e-9), app
        assert chip.infer_counters.noc.slot_cycles == 100


# ---------------------------------------------------------------------------
# Table III: mapping core counts
# ---------------------------------------------------------------------------

def test_kdd_shares_into_one_core():
    """Table III: the 41-15-41 anomaly network runs on ONE core under
    routing-switch loopback sharing (Fig. 2)."""
    assert map_network([41, 15, 41], share_small_layers=True).cores == 1
    assert map_network([41, 15, 41]).cores == 2
    # pretraining provisions the temporary decoders too; sharing still
    # halves the placed cores
    assert map_autoencoder_pretraining(
        [41, 15, 41], share_small_layers=True).cores == 2


def test_feedforward_core_counts_pinned():
    golden = {"mnist_class": 13, "mnist_ae": 13, "isolet_class": 160,
              "isolet_ae": 160, "kdd_anomaly": 2}
    for app, cores in golden.items():
        assert map_network(hw.PAPER_NETWORKS[app]).cores == cores, app


def test_pretraining_core_counts_pinned():
    golden = {"mnist_class": 27, "isolet_class": 327, "kdd_anomaly": 4}
    for app, cores in golden.items():
        nmap = map_autoencoder_pretraining(hw.PAPER_NETWORKS[app])
        assert nmap.cores == cores, app


@pytest.mark.parametrize("app,golden", [
    # (path cores, frozen-prefix cores, step time us) per hidden layer
    ("isolet_ae", [(109, 0, 42.65), (210, 60, 84.41), (180, 130, 72.18),
                   (164, 150, 67.45), (163, 159, 66.57)]),
    ("mnist_ae", [(17, 0, 11.06), (14, 9, 9.91), (14, 11, 10.18),
                  (14, 12, 10.05)]),
])
def test_pretrain_step_cost_pinned(app, golden):
    """One greedy pretraining step per hidden layer: the frozen prefix's
    forward plus the (encoder, temporary decoder) pair's three phases.
    The pairs' own parts, summed over the schedule, are the pretraining
    map's training iteration (`network_cost(pretraining=True)`)."""
    dims = hw.PAPER_NETWORKS[app]
    costs = [hw.pretrain_step_cost(app, dims, k)
             for k in range(len(dims) - 1)]
    assert [(c.cores, c.frozen_cores) for c in costs] == \
        [g[:2] for g in golden]
    for c, g in zip(costs, golden):
        assert c.step.time_us == pytest.approx(g[2], abs=1e-9)
        assert c.step.time_us == pytest.approx(
            c.frozen.time_us + c.pair.time_us, abs=1e-9)
    whole = hw.network_cost(app, dims, pretraining=True)
    assert sum(c.pair.time_us for c in costs) == pytest.approx(
        whole.train.time_us, rel=1e-12)


# ---------------------------------------------------------------------------
# Analytic model outputs (the quantities the <=1% contract compares against)
# ---------------------------------------------------------------------------

def test_kdd_analytic_cost_pinned():
    c = hw.network_cost("kdd_anomaly", [41, 15, 41])
    assert c.train.time_us == pytest.approx(4.42, abs=1e-9)
    assert c.infer.time_us == pytest.approx(0.82, abs=1e-9)
    assert c.train.energy_j == pytest.approx(1.4587896e-08, rel=1e-9)
    assert c.infer.energy_j == pytest.approx(4.2876e-10, rel=1e-9)
    assert c.io_energy_train_j == pytest.approx(3.895e-11, rel=1e-9)
    assert c.io_energy_infer_j == pytest.approx(2.255e-11, rel=1e-9)


def test_mnist_analytic_cost_pinned():
    dims = hw.PAPER_NETWORKS["mnist_class"]
    c = hw.network_cost("mnist_class", dims)
    assert c.cores == 13
    assert c.train.time_us == pytest.approx(12.83, abs=1e-9)
    assert c.infer.time_us == pytest.approx(5.63, abs=1e-9)
    assert c.train.energy_j == pytest.approx(9.4865056e-08, rel=1e-9)


def test_farm_cost_pinned():
    fc = hw.farm_cost("kdd_anomaly", [41, 15, 41], 4)
    assert fc.beat_us == pytest.approx(0.77, abs=1e-9)
    assert fc.serve_samples_per_s == pytest.approx(4e6 / 0.77, rel=1e-9)
    assert fc.reconcile_bits == 2 * 2 * 400 * 100 * 8
    assert fc.train_step_us == pytest.approx(84.42, abs=1e-6)


# ---------------------------------------------------------------------------
# DESIGN.md §5 contract: measured vs analytic <= 1%
# ---------------------------------------------------------------------------

@pytest.mark.sim
def test_measured_vs_analytic_contract_holds():
    """The golden form of the §5.3 contract: one recognition pass and one
    training step on the kdd chip agree with the analytic model to <= 1%
    on every priced quantity."""
    chip = _chip("kdd_anomaly")
    dims = hw.PAPER_NETWORKS["kdd_anomaly"]
    x = jax.random.uniform(jax.random.PRNGKey(9), (1, dims[0]),
                           minval=-0.5, maxval=0.5)
    chip.infer(x)
    chip.train_step(x, x, lr=0.1)
    errs = chip.report().compare_hw(hw.network_cost("kdd_anomaly", dims))
    assert errs and all(v <= 0.01 for v in errs.values()), errs
