"""Closed-loop greedy layer-wise pretraining: one hidden layer's step
driven step after step, and the comparison of its first three steps with
the plain reference (`bench/reference_ae.py`).

The loop and its comparison are `bench/kinds/train.py`'s, on the pair:
set-up takes the program's object from the driver, draws a pool of
``pool`` batches of ``batch`` network inputs from the seed on the device
(no targets: the target is the representation the program computes),
and drives the object through its first three steps of hidden layer
``layer`` with the window's own call.  The loop keeps each step's
reconstruction error and the pair's conductances after steps 1 and 3.

After the window the reference repeats the three steps from the same
conductances, and five numbers are compared:

  * ``err_gap_median``, ``grad_gap``, ``change_gap`` and ``xval`` as in
    `train`, over the pair's four leaves (encoder ``layer``, decoder
    ``layer``); ``xval`` holds the program's pretraining counters to
    `hw_model.pretrain_step_cost`;
  * ``frozen_gap``: after the window, the largest ``|change|`` of any
    leaf of the frozen encoders ``0 .. layer-1``.  A sound program never
    writes them, so it reads 0.

`Loop.controls` names what ``bench/readings.py`` puts in the program's
place: the reference one precision below the configuration's, the
reference that updates from half the batch, the reference whose target
is the representation before its ADC, and the reference in which the
decoder hands no error back to the encoder (over two modelled chips, the
error that crosses the boundary).
"""
from __future__ import annotations

import numpy as np

from bench import reference as ref
from bench import reference_ae
from bench import work_pretrain
from bench.kinds import train


def sound(prefix):
    """The reference's step behind the frozen ``prefix``, in the shape
    `train.reference_steps` calls."""
    def step(pair, x, t, lr, spec, precision):
        return reference_ae.pretrain_step(prefix, pair, x, lr, spec,
                                          precision)
    return step


def target_before_adc(prefix):
    """Fault: the target is the representation before its 3-bit ADC; the
    encoder's input is the codes."""
    def step(pair, x, t, lr, spec, precision):
        r = reference_ae.representation(prefix, x, spec, precision)
        raw = reference_ae.representation(prefix, x, spec, precision,
                                          quantize=False)
        return ref.train_step(pair, r, raw, lr, spec, precision)
    return step


def half_batch(prefix):
    """Fault: the update from half the batch, the mean taken over the
    rest; the reconstruction error of the whole batch."""
    def step(pair, x, t, lr, spec, precision):
        h = x.shape[0] // 2
        new, _ = sound(prefix)(pair, x[:h], t, lr, spec, precision)
        _, err = sound(prefix)(pair, x, t, lr, spec, precision)
        return new, err
    return step


def boundary_error_zeroed(prefix):
    """Fault: no error handed back from the decoder to the encoder, so
    only the decoder is updated; over two modelled chips, the error that
    crosses the boundary is lost."""
    def step(pair, x, t, lr, spec, precision):
        new, err = sound(prefix)(pair, x, t, lr, spec, precision)
        return (pair[0], new[1]), err
    return step


class Loop(train.Loop):
    """A closed loop of ``step(x)`` calls on a seeded pool.

    The driver hands over ``step`` (the program's pretraining call at the
    traffic's layer, returning the reconstruction error), ``pair`` and
    ``prefix`` (the pair's and the frozen encoders' conductances as
    (g_plus, g_minus) pairs) and ``xval``."""

    def __init__(self, ctx, program: dict):
        import jax
        self.ctx, self.program = ctx, program
        tr = ctx.traffic
        self.layer = int(tr["layer"])
        self.batch, self.lr = int(tr["batch"]), float(tr["lr"])
        dims = ctx.cfg["dims"]
        #: passes over the network per sample: the step's operations over
        #: a recognition pass of ``dims`` (`work_pretrain.passes`); at
        #: layer 1 of isolet_ae (617*2000 + 3*(2000*1000 + 1000*2000))
        #: / 3,864,000 = 3.4249
        self.PASSES = work_pretrain.passes(dims, self.layer)
        pool = int(tr["pool"])
        xs = ref.uniform_pool(ctx.seed, 1, (pool, self.batch, dims[0]))
        self.pool = [xs[i] for i in range(pool)]
        jax.block_until_ready(self.pool)
        dec = reference_ae.initial_decoders(ctx.cfg, ctx.seed)[self.layer]
        self.g0_pair = [ctx.g0_host[self.layer],
                        tuple(np.asarray(a) for a in dec)]
        self.prefix0 = ctx.g0_host[:self.layer]
        errs = []
        for i in range(train.REF_STEPS):
            err = jax.block_until_ready(self.call(self.feed(i)))
            errs.append(np.asarray(err, np.float64))
            if i == 0:
                after1 = train.host_leaves(program["pair"]())
        self.prog = (errs, after1, train.host_leaves(program["pair"]()))
        self.first_call = train.REF_STEPS

    def call(self, x):
        return self.program["step"](x)

    def finish(self) -> dict:
        prefix = train.host_leaves(self.program["prefix"]())
        gap = max((float(np.max(np.abs(a - b))) for a, b in
                   zip(prefix, train.host_leaves(self.prefix0))),
                  default=0.0)
        return {**super().finish(), "frozen_gap": gap}

    def controls(self) -> dict:
        return {"bf16_3x": {"precision": "bf16_3x"},
                "half_batch": {"fault": half_batch},
                "target_before_adc": {"fault": target_before_adc},
                "boundary_error_zeroed": {"fault": boundary_error_zeroed}}

    def batches(self):
        return [(self.pool[i], None) for i in range(train.REF_STEPS)]

    def check(self, precision: str = "highest", fault=None,
              look: bool = False) -> dict:
        """Program against reference on the pair; with ``precision``
        below the configuration's or a ``fault`` (a step maker taking the
        frozen prefix), the reference so computed is put in the
        program's place.  ``look`` adds ``loss_gap``."""
        import jax.numpy as jnp
        spec = self.ctx.spec
        prefix = tuple((jnp.asarray(a), jnp.asarray(b))
                       for a, b in self.prefix0)
        refd = train.reference_steps(self.g0_pair, self.batches(), self.lr,
                                     spec, step=sound(prefix))
        prog = self.prog
        if precision != "highest" or fault is not None:
            prog = train.reference_steps(
                self.g0_pair, self.batches(), self.lr, spec, precision,
                (fault or sound)(prefix))
        out = train.compare(self.g0_pair, prog, refd)
        if look:
            out["loss_gap"] = train.loss_gap(prog, refd)
        return out
