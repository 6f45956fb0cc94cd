"""Closed-loop serving: sessions of single-row requests, and the comparison
of every answer served in the window with the plain reference.

Each call submits one session of ``requests`` rows, drawn from a seeded
pool of ``pool`` sessions, and waits for all of its answers on the host.
Every request of a session is timed from the session's submission to its
answer being ready, so each of its requests has the session's latency.
Set-up serves ``warm`` sessions, which compiles and warms every shape.

After the window every answer served in it is compared with the
reference's output for its row.  A 3-bit activation code whose sum lies at
a level boundary may round either way in a sound program, and the flip
moves the answer downstream by as much as an error would; the reference
follows every such choice (`reference.forward_candidates`), and each
answer is held to the nearest of its row's candidates:

  * ``out_gap_median``: the median over answers of each answer's widest
    gap.  The output layer is not quantized, so this reads the precision
    of the contractions: float re-association against three bfloat16
    passes;
  * ``out_gap_max``: the widest gap of any answer: an answer altered by
    one step of the output ADC moves it by 0.14;
  * ``xval``: the program's counters against its analytic model.

`Loop.controls` names what ``bench/readings.py`` puts in the program's
place to read the upper ends of the limits: the reference one precision
below the configuration's, and the reference with one answer altered.
"""
from __future__ import annotations

import numpy as np

from bench import reference as ref


def answer_altered(g0, x, spec, precision):
    """Fault: the first answer moved by one step of the output ADC where
    it is produced."""
    out = ref.forward(g0, x, spec, precision)
    return out.at[0].add(1.0 / (2 ** spec.adc_bits - 1))


class Loop:
    """A closed loop of ``serve(x)`` sessions on a seeded pool.

    The driver hands over ``serve`` (the program's serve call, returning
    the answers in request order) and ``xval``."""

    #: the comparison reads every answer the window served
    CHECKS_WINDOW = True
    #: passes over the network per sample: recognition only
    PASSES = 1

    def __init__(self, ctx, program: dict):
        import jax
        self.ctx, self.program = ctx, program
        tr = ctx.traffic
        self.requests = int(tr["requests"])
        dims = ctx.cfg["dims"]
        pool = int(tr["pool"])
        xs = ref.uniform_pool(ctx.seed, 1, (pool, self.requests, dims[0]))
        self.pool = [xs[i] for i in range(pool)]
        jax.block_until_ready(self.pool)
        self.served: list[tuple[int, object]] = []
        for i in range(int(tr["warm"])):
            jax.block_until_ready(self.call(self.feed(i)))
        self.first_call = 0

    def feed(self, i: int):
        return self.pool[i % len(self.pool)]

    def call(self, x):
        return self.program["serve"](x)

    def done(self, i: int, result) -> int:
        """Keep the session's answers for the check; samples completed."""
        self.served.append((i % len(self.pool), result))
        return int(result.shape[0])

    def end_to_end(self, win: dict) -> dict:
        from bench.clock import percentile
        per_request = []
        for t in win["latencies"]:
            per_request += [t] * self.requests
        return {"samples_per_s": win["samples"] / win["window_s"],
                "serve_p95_ms": 1e3 * percentile(per_request, 95)}

    def finish(self) -> dict:
        xval = self.program["xval"]()
        self.served = [(p, np.asarray(o, np.float64)) for p, o in self.served]
        self.program = None
        return {"xval": max(xval.values())}

    def controls(self) -> dict:
        """What ``bench/readings.py`` puts in the program's place, by name:
        keyword arguments of `check`."""
        return {"bf16_3x": {"precision": "bf16_3x"},
                "answer_altered": {"forward": answer_altered}}

    def check(self, precision: str = "highest", forward=None,
              look: bool = False) -> dict:
        """The compared numbers over every answer served in the window.
        ``precision`` below the configuration's, or a faulted ``forward``,
        puts the reference so computed in the program's place.  ``look``
        adds nothing here."""
        import jax.numpy as jnp
        g0 = tuple((jnp.asarray(a), jnp.asarray(b))
                   for a, b in self.ctx.g0_host)
        spec = self.ctx.spec
        swap = precision != "highest" or forward is not None
        fwd = forward or ref.forward
        want, got = {}, {}
        for p in sorted({p for p, _ in self.served}):
            want[p] = ref.forward_candidates(g0, self.pool[p], spec)
            if swap:
                got[p] = np.asarray(fwd(g0, self.pool[p], spec, precision),
                                    np.float64)
        gaps = np.concatenate([
            ref.nearest_gaps(got.get(p, out), *want[p])
            for p, out in self.served])
        return {"out_gap_median": float(np.median(gaps)),
                "out_gap_max": float(gaps.max())}
