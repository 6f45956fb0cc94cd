"""Closed-loop recognition: batches of new input through the program's
recognition call, and the comparison of its answers with the plain
reference, as `bench/kinds/serve.py` compares them.

Each call submits one batch of ``batch`` rows, drawn from a seeded pool
of ``pool`` batches, and waits for its outputs on the host.  Set-up
makes ``warm`` calls, which compile and warm the one shape.  Recognition
leaves the conductances as they were, so every call on one batch of the
pool has one answer; the loop keeps the window's last answer to each
batch, and after the window every kept answer is held to the nearest of
its row's reference candidates: ``out_gap_median``, ``out_gap_max`` and
``xval`` (the recognition counters against the analytic model).
"""
from __future__ import annotations

from bench import reference as ref
from bench.kinds import serve


class Loop(serve.Loop):
    """A closed loop of ``infer(x)`` calls on a seeded pool.

    The driver hands over ``infer`` (the program's recognition call,
    returning the outputs of the batch's rows) and ``xval``."""

    def __init__(self, ctx, program: dict):
        import jax
        self.ctx, self.program = ctx, program
        tr = ctx.traffic
        self.batch = int(tr["batch"])
        dims = ctx.cfg["dims"]
        pool = int(tr["pool"])
        xs = ref.uniform_pool(ctx.seed, 1, (pool, self.batch, dims[0]))
        self.pool = [xs[i] for i in range(pool)]
        jax.block_until_ready(self.pool)
        self.last: dict = {}
        self.served = []
        for i in range(int(tr["warm"])):
            jax.block_until_ready(self.call(self.feed(i)))
        self.first_call = 0

    def call(self, x):
        return self.program["infer"](x)

    def done(self, i: int, result) -> int:
        """Keep the batch's last answer for the check; samples completed."""
        self.last[i % len(self.pool)] = result
        return self.batch

    def end_to_end(self, win: dict) -> dict:
        return {"samples_per_s": win["samples"] / win["window_s"]}

    def finish(self) -> dict:
        self.served, self.last = sorted(self.last.items()), {}
        return super().finish()
