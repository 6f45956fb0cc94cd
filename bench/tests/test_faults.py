"""Whole runs on the CPU, at a small size, with the timed path broken
underneath: each fault a cell can have must turn ``correct`` false, and
the sound program must keep it true.

The harness's look for a chip is skipped (``require_chip=False``); the
rest of a run is the one the chip runs: set-up, window, the comparison
with the reference and the cell's committed limits.  Faults are planted in
the program's compiled entry points:

  * ``unchanged``: the step returns its state unchanged (learning rate 0);
  * ``half_batch``: the update from half the batch, the mean taken over
    the rest;
  * ``hidden_error``: on one chip, no error handed back past the output
    layer (only the output stage is updated); over the two-chip pipeline,
    no error handed back from the second modelled chip to the first;
  * ``altered``: one answer moved by one 3-bit ADC step where it is
    produced.
"""
import pytest

from bench import run as harness

SEED = 3_000_000_011
SMALL = {"dims": [100, 40, 10], "core_rows": 40, "core_cols": 10}
ADC_STEP = 1.0 / 7.0          # one step of the 3-bit output ADC


def small(workload):
    if "serve" in workload:
        return {"config": dict(SMALL, modelled_chips=2),
                "traffic": {"requests": 8, "pool": 2, "warm": 1}}
    return {"config": dict(SMALL, modelled_chips=2),
            "traffic": {"batch": 64, "pool": 4}}


def one_run(workload):
    res = harness.run(workload, SEED, 0.5, False, require_chip=False,
                      overrides=small(workload))
    return res["correct"], res["checks"]


def plant_train(monkeypatch, name, fault):
    """Break the compiled training entry ``name`` of `sim.compiled`."""
    from repro.sim import compiled as csim
    orig = getattr(csim, name)

    def broken(gp, gm, *args, lr_eff=0.1, **kw):
        if fault == "unchanged":
            return orig(gp, gm, *args, lr_eff=0.0, **kw)
        # half_batch: the error of the whole batch, the update of half
        out = orig(gp, gm, *args, lr_eff=0.0, **kw)
        # the batch axis is next to last in every operand but the
        # trailing index maps and config
        halves = [a[..., :a.shape[-2] // 2, :] for a in args[:-2]]
        half = orig(out[0], out[1], *halves, *args[-2:],
                    lr_eff=2 * lr_eff, **kw)
        return (half[0], half[1]) + tuple(out[2:])

    monkeypatch.setattr(csim, name, broken)


def plant_hidden_error(monkeypatch, name):
    """Break the error handed back past the output layer (``chip_train``)
    or between pipeline slices (``chip_backward``)."""
    import jax.numpy as jnp

    from repro.sim import compiled as csim
    orig = getattr(csim, name)

    def output_stage_only(gp, gm, *args, **kw):
        # the program donates its stacks: keep copies of the state before
        keep_p, keep_m = jnp.array(gp), jnp.array(gm)
        out = orig(gp, gm, *args, **kw)
        return (keep_p.at[-1].set(out[0][-1]),
                keep_m.at[-1].set(out[1][-1])) + tuple(out[2:])

    def no_handoff(*args, **kw):
        gp2, gm2, delta, cnt = orig(*args, **kw)
        return gp2, gm2, jnp.zeros_like(delta), cnt

    monkeypatch.setattr(csim, name, output_stage_only
                        if name == "chip_train" else no_handoff)


@pytest.mark.parametrize("workload", ["mnist_class.chip_train.b64",
                                      "isolet_class.pipeline_train.b64",
                                      "isolet_class.pipeline_serve.q64"])
def test_sound_program_is_correct(workload):
    ok, checks = one_run(workload)
    assert ok, checks


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
@pytest.mark.parametrize("workload,entry", [
    ("mnist_class.chip_train.b64", "chip_train"),
    ("isolet_class.pipeline_train.b64", "chip_backward")])
def test_training_fault_is_caught(monkeypatch, workload, entry, fault):
    plant_train(monkeypatch, entry, fault)
    ok, checks = one_run(workload)
    assert not ok, checks


@pytest.mark.parametrize("workload,entry", [
    ("mnist_class.chip_train.b64", "chip_train"),
    ("isolet_class.pipeline_train.b64", "chip_backward")])
def test_hidden_error_fault_is_caught(monkeypatch, workload, entry):
    plant_hidden_error(monkeypatch, entry)
    ok, checks = one_run(workload)
    assert not ok, checks
    assert checks["grad_gap"]["value"] > checks["grad_gap"]["limit"], checks


def test_altered_answer_is_caught(monkeypatch):
    from repro.sim import compiled as csim
    orig = csim.serve_scan

    def altered(*args, **kw):
        out = orig(*args, **kw)
        return out.at[0].add(ADC_STEP)

    monkeypatch.setattr(csim, "serve_scan", altered)
    ok, checks = one_run("isolet_class.pipeline_serve.q64")
    assert not ok, checks
