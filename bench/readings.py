#!/usr/bin/env python3
"""Read the numbers that set a cell's limits, on the chip, at the cell's
own size, in one process:

  python3 bench/readings.py --workload <name> --seeds 1,2,3 \
      --control-seeds 4,5,6 [--seconds <s>] [--out <file>]

For each of ``--seeds`` the program is built and driven as a run drives it
and its compared numbers are read: the lower readings.  Set-up is a run's
own; where the cell's loop compares what its window produced
(``Loop.CHECKS_WINDOW``), the loop also runs a window of ``--seconds``
(by default the benchmark's ``run_seconds``), so that as many answers are
compared as a run compares.  For each of ``--control-seeds`` the same
set-up is made and each entry of the loop's ``controls()`` is put in the
program's place: the reference one precision below the configuration's
and the reference with each fault the cell can have.  Those are the upper
readings.  Numbers a loop reads for the record but does not compare
(``check(look=True)``) are printed beside them.

The benchmark's own runs never run this.  Each line of output is one JSON
object: seed, which side, and the numbers.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench import run as harness
    cell = harness.resolve(args.workload)
    seconds = args.seconds if args.seconds is not None \
        else float(cell["run_seconds"])
    harness.setup_jax(True)
    devices = harness.chips_or_fail(int(cell["workload"]["chips"]))
    kind = harness.load_module(os.path.join(
        BENCH, "kinds", cell["traffic"]["kind"] + ".py"))
    driver = harness.load_module(os.path.join(
        BENCH, "drivers", cell["traffic"]["driver"] + ".py"))
    with open(args.out, "a") if args.out else contextlib.nullcontext() \
            as out:
        def emit(rec):
            line = json.dumps(rec)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()

        seeds = [("program", int(s)) for s in args.seeds.split(",") if s]
        seeds += [("control", int(s))
                  for s in args.control_seeds.split(",") if s]
        for side, seed in seeds:
            ctx = harness.Context(cell, seed, devices)
            loop = kind.Loop(ctx, driver.build(ctx))
            calls = 0
            if loop.CHECKS_WINDOW:
                calls = harness.window(loop, seconds, None)["calls"]
            state = loop.finish()
            if side == "program":
                emit({"seed": seed, "side": "program",
                      "window_calls": calls, **loop.check(look=True),
                      **state})
                continue
            for name, kw in loop.controls().items():
                emit({"seed": seed, "side": name,
                      **loop.check(look=True, **kw)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
