"""Closed-loop training of a conv network: one compiled step object
driven step after step, and the comparison of its first three steps with
the plain reference (`bench/reference_conv.py`).

The loop and its comparison are `bench/kinds/train.py`'s, over every
layer of the network, the conv kernels first: set-up takes the
program's object from the driver, draws a pool of ``pool`` batches of
``batch`` input maps (the first conv layer's ``H*W*C`` lanes, stream 1)
and targets (stream 2) from the seed on the device, draws the conv
kernels as the driver does (stream 4), and drives the object through its
first three steps with the window's own call.  The loop keeps each
step's output error and every layer's conductances after steps 1 and 3
on the host.

After the window the reference repeats the three steps from the same
conductances, and the numbers of `train` are compared:
``err_gap_median``, ``grad_gap`` and ``change_gap`` over the leaves of
every layer (a conv layer's pair among them), and ``xval``, the
program's counters against `hw_model.conv_network_cost`, each conv
layer's core steps, routed codes and pool sums included.

`Loop.controls` names what ``bench/readings.py`` puts in the program's
place: the reference one precision below the configuration's, and the
reference with each fault a conv stage can have: each conv update taken
from the first position only (``position0``), every patch read one lane
along the map (``shifted``), and the pool's error handed back without
the division by its window (``no_quarter``).
"""
from __future__ import annotations

import numpy as np

from bench import reference as ref
from bench import reference_conv
from bench import work_conv
from bench.kinds import train


def stepper(geom, fault=None):
    """The reference's step, in the shape `train.reference_steps`
    calls."""
    def step(layers, x, t, lr, spec, precision):
        return reference_conv.train_step(geom, layers, x, t, lr, spec,
                                         precision, fault)
    return step


class Loop(train.Loop):
    """A closed loop of ``step(x, target)`` calls on a seeded pool.

    The driver hands over ``step``, ``layers`` (every layer's
    conductances as (g_plus, g_minus) pairs, the conv kernels first) and
    ``xval``, as for `train`."""

    def __init__(self, ctx, program: dict):
        import jax
        self.ctx, self.program = ctx, program
        tr = ctx.traffic
        cfg = ctx.cfg
        self.batch, self.lr = int(tr["batch"]), float(tr["lr"])
        self.geom = reference_conv.geometry(cfg)
        #: passes over the dense chain per sample (`work_conv.passes`):
        #: 3 * 416,520 / 58,920 = 21.208 for LeNet-5
        self.PASSES = work_conv.passes(cfg)
        (H, W, C), pool = cfg["conv"][0]["in"], int(tr["pool"])
        xs = ref.uniform_pool(ctx.seed, 1, (pool, self.batch, H * W * C))
        ts = ref.uniform_pool(ctx.seed, 2, (pool, self.batch,
                                            cfg["dims"][-1]))
        self.pool = [(xs[i], ts[i]) for i in range(pool)]
        jax.block_until_ready(self.pool)
        kernels = reference_conv.initial_conv_layers(cfg, ctx.seed)
        self.g0 = [tuple(np.asarray(a) for a in p) for p in kernels] \
            + list(ctx.g0_host)
        errs = []
        for i in range(train.REF_STEPS):
            err = jax.block_until_ready(self.call(self.feed(i)))
            errs.append(np.asarray(err, np.float64))
            if i == 0:
                after1 = train.host_leaves(program["layers"]())
        self.prog = (errs, after1, train.host_leaves(program["layers"]()))
        self.first_call = train.REF_STEPS

    def controls(self) -> dict:
        return {"bf16_3x": {"precision": "bf16_3x"},
                "position0_update": {"fault": "position0"},
                "patches_shifted": {"fault": "shifted"},
                "pool_error_whole": {"fault": "no_quarter"}}

    def check(self, precision: str = "highest", fault=None,
              look: bool = False) -> dict:
        """Program against reference; with ``precision`` below the
        configuration's or a ``fault`` (`reference_conv`), the reference
        so computed is put in the program's place.  ``look`` adds
        ``loss_gap``."""
        spec = self.ctx.spec
        refd = train.reference_steps(self.g0, self.batches(), self.lr, spec,
                                     step=stepper(self.geom))
        prog = self.prog
        if precision != "highest" or fault is not None:
            prog = train.reference_steps(self.g0, self.batches(), self.lr,
                                         spec, precision,
                                         stepper(self.geom, fault))
        out = train.compare(self.g0, prog, refd)
        if look:
            out["loss_gap"] = train.loss_gap(prog, refd)
        return out
