"""Closed-loop data-parallel training of a farm on a mesh: the loop,
warm-up and comparison of `bench/kinds/train.py`, with the pool's batches
placed on the mesh as a data-parallel input pipeline feeds them.

Set-up draws the pool from the seed as `train.Loop` does and places every
batch on the chips, split by rows in replica order (the driver's
``rows`` sharding), before the first step; the reference is handed the
same rows as drawn.  With the reconciliation exact (``reconcile`` none)
the farm's step is the reference's step on the global batch, so
``err_gap_median``, ``grad_gap``, ``change_gap`` and ``xval`` are read as
for one chip, from replica 0's conductances.  One more number is compared:

  * ``replica_gap``: after the window, the largest ``|replica c -
    replica 0|`` over every leaf and replica.  Every replica writes the
    same reconciled pulses, so a sound farm reads 0: any gap is a replica
    that missed or mis-applied an update.

`Loop.controls` adds ``exchange_skipped`` to training's controls: replica
0's update from its own rows alone at the global batch's learning rate,
which is what a replica writes when the reconciliation is missing.
"""
from __future__ import annotations

import numpy as np

from bench import reference as ref
from bench.kinds import train


def exchange_skipped(replicas: int):
    """Fault: the update replica 0 makes from its own ``batch / replicas``
    rows, each weighted as a row of the whole batch; the error of the
    whole batch."""
    def step(layers, x, t, lr, spec, precision):
        m = x.shape[0] // replicas
        new, _ = ref.train_step(layers, x[:m], t[:m], lr * m / x.shape[0],
                                spec, precision)
        _, err = ref.train_step(layers, x, t, lr, spec, precision)
        return new, err
    return step


def replica_gap(replicas) -> float:
    """Largest ``|replica c - replica 0|`` over every leaf and replica
    (replicas as lists of (g_plus, g_minus) pairs)."""
    base = train.host_leaves(replicas[0])
    return max((float(np.max(np.abs(a - b)))
                for other in replicas[1:]
                for a, b in zip(train.host_leaves(other), base)),
               default=0.0)


class Loop(train.Loop):
    """`train.Loop` over a pool placed on the mesh.  The driver also hands
    over ``rows`` (the sharding of a batch) and ``replicas`` (every
    replica's conductances as (g_plus, g_minus) pairs)."""

    def __init__(self, ctx, program: dict):
        self.placed = []
        super().__init__(ctx, program)

    def feed(self, i: int):
        if not self.placed:
            # the first call, in set-up: the whole pool at once
            import jax
            rows = self.program["rows"]
            self.placed = [tuple(jax.device_put(a, rows) for a in batch)
                           for batch in self.pool]
            jax.block_until_ready(self.placed)
        return self.placed[i % len(self.placed)]

    def finish(self) -> dict:
        gap = replica_gap(self.program["replicas"]())
        self.placed = []
        return {**super().finish(), "replica_gap": gap}

    def controls(self) -> dict:
        return {**super().controls(), "exchange_skipped": {
            "step": exchange_skipped(int(self.ctx.cfg["replicas"]))}}
