"""Closed-loop training: one compiled step object driven step after step,
and the comparison of its first three steps with the plain reference.

Set-up takes the program's object from the driver, draws a pool of
``pool`` batches of ``batch`` rows from the seed on the device, and drives
the object through its first three steps with the window's own call on the
first three batches (rows that all differ).  Those steps compile and warm
every shape the window uses.  The loop keeps each step's output error and
the conductances after steps 1 and 3 on the host; the window then goes on
with step 4 on the same object.

After the window the reference repeats the three steps from the same
conductances, and four numbers are compared:

  * ``err_gap_median``: step 1's output error, the median over the rows
    of each row's widest gap to the reference's.  The output layer is not
    quantized, so this reads the precision of the contractions.  The
    median, and not each step's loss, because a 3-bit activation code
    whose sum lies at a level boundary may round either way in a sound
    program; such a flip moves the row's error, and through the pulse
    updates every later step's, by as much as an error would;
  * ``grad_gap``: the first update as the conductances took it (state after
    step 1 less the initial state), by the worst leaf;
  * ``change_gap``: the change after three steps, by the worst leaf;
  * ``xval``: the program's counters against its analytic model (the 1%
    contract of ``core/hw_model.py``).

A leaf is one conductance array (``g_plus`` or ``g_minus`` of a layer).
The gap of a leaf is ``| |d_prog| - |d_ref| |`` over the larger of
``|d_ref|`` and the median of the leaves that the reference moves.  The
configurations start from conductances spread over the device's range, so
the reference moves every leaf in step 1: the hidden layers' updates check
the errors handed back through every layer, and between modelled chips.

`Loop.controls` names what ``bench/readings.py`` puts in the program's
place to read the upper ends of the limits: the reference one precision
below the configuration's, and the reference with each fault a training
cell can have.  A step that returns its state unchanged reads 1 in
``grad_gap`` and ``change_gap`` by their definition and needs no run.
"""
from __future__ import annotations

import statistics

import numpy as np

from bench import reference as ref

REF_STEPS = 3


def host_leaves(layers) -> list[np.ndarray]:
    """Leaves as float64 host arrays, from (g_plus, g_minus) pairs."""
    return [np.asarray(a, np.float64) for pair in layers for a in pair]


def leaf_gap(prog: list, ref_: list) -> float:
    """Worst-leaf gap of two lists of per-leaf changes."""
    pn = [float(np.linalg.norm(a)) for a in prog]
    rn = [float(np.linalg.norm(a)) for a in ref_]
    moving = [r for r in rn if r > 0.0]
    if not moving:
        return 0.0 if max(pn) == 0.0 else float("inf")
    med = statistics.median(moving)
    return max(abs(p - r) / max(r, med) for p, r in zip(pn, rn))


def loss(err) -> float:
    e = np.asarray(err, np.float64)
    return float(np.mean(e * e))


def reference_steps(g0, batches, lr: float, spec, precision: str = "highest",
                    step=None):
    """The reference's first steps from ``g0`` (host (g_plus, g_minus)
    pairs): (errors, leaves after step 1, leaves after the last step).
    ``step`` puts another step function (a fault) in the reference's
    place."""
    import jax.numpy as jnp
    step = step or ref.train_step
    layers = tuple((jnp.asarray(a), jnp.asarray(b)) for a, b in g0)
    errs, after1 = [], None
    for k, (x, t) in enumerate(batches):
        layers, err = step(layers, x, t, lr, spec, precision)
        errs.append(np.asarray(err, np.float64))
        if k == 0:
            after1 = host_leaves(layers)
    return errs, after1, host_leaves(layers)


def compare(g0, prog: tuple, refd: tuple) -> dict:
    """The compared numbers from the program's readings ``prog`` (errors,
    after step 1, after step 3) and the reference's ``refd``."""
    p_err, p1, p3 = prog
    r_err, r1, r3 = refd
    leaves0 = host_leaves(g0)
    d = lambda after: [a - b for a, b in zip(after, leaves0)]  # noqa: E731
    return {"err_gap_median": float(np.median(
                np.abs(p_err[0] - r_err[0]).max(axis=1))),
            "grad_gap": leaf_gap(d(p1), d(r1)),
            "change_gap": leaf_gap(d(p3), d(r3))}


def loss_gap(prog: tuple, refd: tuple) -> float:
    """The worst step's ``|L - L_ref| / L_ref`` (L the mean squared output
    error): read for the record, not compared."""
    return max(abs(loss(p) - loss(r)) / loss(r)
               for p, r in zip(prog[0], refd[0]))


def half_batch(layers, x, t, lr, spec, precision):
    """Fault: the update from half the batch, the mean taken over the
    rest; the error of the whole batch."""
    h = x.shape[0] // 2
    new, _ = ref.train_step(layers, x[:h], t[:h], lr, spec, precision)
    _, err = ref.train_step(layers, x, t, lr, spec, precision)
    return new, err


def hidden_error_zeroed(layers, x, t, lr, spec, precision):
    """Fault: no error handed back past the output layer, so only the
    output layer is updated; between two modelled chips the same fault
    leaves the first chip's layers where they were."""
    new, err = ref.train_step(layers, x, t, lr, spec, precision)
    return tuple(layers[:-1]) + (new[-1],), err


class Loop:
    """A closed loop of ``step(x, target)`` calls on a seeded pool.

    The driver hands over ``step`` (the program's train call), ``layers``
    (its conductances as (g_plus, g_minus) pairs) and ``xval`` (its
    counters' relative errors against the analytic model, by name)."""

    #: the comparison reads set-up's steps, not the window's
    CHECKS_WINDOW = False
    #: passes over the network per sample: forward, backward, update
    PASSES = 3

    def __init__(self, ctx, program: dict):
        import jax
        self.ctx, self.program = ctx, program
        tr = ctx.traffic
        self.batch, self.lr = int(tr["batch"]), float(tr["lr"])
        dims = ctx.cfg["dims"]
        pool = int(tr["pool"])
        xs = ref.uniform_pool(ctx.seed, 1, (pool, self.batch, dims[0]))
        ts = ref.uniform_pool(ctx.seed, 2, (pool, self.batch, dims[-1]))
        self.pool = [(xs[i], ts[i]) for i in range(pool)]
        jax.block_until_ready(self.pool)
        errs = []
        for i in range(REF_STEPS):
            err = jax.block_until_ready(self.call(self.feed(i)))
            errs.append(np.asarray(err, np.float64))
            if i == 0:
                after1 = host_leaves(program["layers"]())
        self.prog = (errs, after1, host_leaves(program["layers"]()))
        self.first_call = REF_STEPS

    def feed(self, i: int):
        return self.pool[i % len(self.pool)]

    def call(self, args):
        x, t = args
        return self.program["step"](x, t)

    def done(self, i: int, result) -> int:
        """Samples this call completed."""
        return self.batch

    def end_to_end(self, win: dict) -> dict:
        return {"samples_per_s": win["samples"] / win["window_s"]}

    def finish(self) -> dict:
        """Read what the program holds after the window, then let it go."""
        xval = self.program["xval"]()
        self.program = None
        return {"xval": max(xval.values())}

    def controls(self) -> dict:
        """What ``bench/readings.py`` puts in the program's place, by name:
        keyword arguments of `check`."""
        return {"bf16_3x": {"precision": "bf16_3x"},
                "half_batch": {"step": half_batch},
                "hidden_error_zeroed": {"step": hidden_error_zeroed}}

    def batches(self):
        return [self.pool[i] for i in range(REF_STEPS)]

    def check(self, precision: str = "highest", step=None,
              look: bool = False) -> dict:
        """Program against reference; with ``precision`` below the
        configuration's or a faulted ``step``, the reference so computed
        is put in the program's place.  ``look`` adds ``loss_gap``."""
        g0, spec = self.ctx.g0_host, self.ctx.spec
        refd = reference_steps(g0, self.batches(), self.lr, spec)
        prog = self.prog
        if precision != "highest" or step is not None:
            prog = reference_steps(g0, self.batches(), self.lr, spec,
                                   precision, step)
        out = compare(g0, prog, refd)
        if look:
            out["loss_gap"] = loss_gap(prog, refd)
        return out
