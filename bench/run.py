#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json`` at
the root of the checkout:

  * ``bench/configs/<config>.json``: the network's widths, core geometry
    and crossbar constraints, with the source they come from;
  * ``bench/traffic/<traffic>.json``: the traffic mix, data only: which
    deployment of the program to build (``driver``), which loop drives it
    (``kind``) and its sizes;
  * ``bench/drivers/<driver>.py``: builds the program's object from the
    configuration and the benchmark's own conductances;
  * ``bench/kinds/<kind>.py``: the closed loop, its warm-up and the
    comparison with the plain reference (``bench/reference.py``);
  * ``bench/limits/<workload>.json``: the limit of each number compared;
  * ``bench/metrics/<metric>.py``: one reader per per-layer metric.

A run draws the conductances and the inputs from ``--seed`` on the device,
builds and warms the cell (``setup_s``), measures a closed loop for
``--seconds``, then frees the program and compares what the window produced
with the reference.  With ``--trace 1`` the window is profiled and the
per-layer metrics are read from the device trace.  The last line of
standard output is one JSON object; the last lines of standard error give
each compared number beside its limit.

The run exits non-zero, printing no result, when JAX finds no accelerator
or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".cache", "jax")
TRACE_DIR = os.path.join(ROOT, ".cache", "bench-trace")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def load_module(path: str):
    """Import one file of the benchmark by its path (names may hold dots)."""
    name = "bench_" + os.path.relpath(path, BENCH).replace(os.sep, "_") \
        .replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, overrides: dict | None = None) -> dict:
    """The cell's entry, configuration, traffic, limits and metrics."""
    bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}")
    w = cells[workload]
    cfg = read_json(os.path.join(BENCH, "configs", w["config"] + ".json"))
    traffic = read_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    limits = read_json(os.path.join(BENCH, "limits", workload + ".json"))
    overrides = overrides or {}
    cfg.update(overrides.get("config", {}))
    traffic.update(overrides.get("traffic", {}))
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", [workload])]
    return {"workload": w, "config": cfg, "traffic": traffic,
            "limits": limits, "end_to_end": e2e, "per_layer": layer,
            "run_seconds": bench["run_seconds"]}


def setup_jax(cache: bool) -> None:
    """Put the program on the path and, for a run on the chip, keep JAX's
    persistent compilation cache in the checkout, whatever the environment
    names, and cache every compile: only the first run of a cell in a
    checkout compiles."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    if cache:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
        import jax
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        # no eviction: a size limit in the environment turns on JAX's LRU
        # bookkeeping, which fails every write once one entry lacks its
        # access-time file, and a cell's programs fit in a few hundred MB
        jax.config.update("jax_compilation_cache_max_size", -1)


def chips_or_fail(n: int):
    """The first ``n`` accelerator devices; `NoChip` otherwise."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoChip("JAX found no accelerator")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


class Context:
    """What the kinds and drivers share: configuration, traffic, seed,
    the program's crossbar constraints and the benchmark's conductances."""

    def __init__(self, cell: dict, seed: int, devices):
        import numpy as np

        from bench import reference as ref
        self.cell = cell
        self.cfg, self.traffic = cell["config"], cell["traffic"]
        self.seed, self.devices = seed, devices
        self.chips = len(devices)
        self.spec = ref.Spec(self.cfg)
        with span("bench.setup.weights"):
            layers = ref.initial_layers(self.cfg, seed)
            # host copies for the reference, taken before the program
            # sees (and may donate) its own
            self.g0_host = [tuple(np.asarray(a) for a in p) for p in layers]
        self.g0 = layers


def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def window(cell, seconds: float, trace_dir: str | None) -> dict:
    """The measured closed loop: calls until ``seconds`` have passed;
    returns per-call latencies, samples and the window's length."""
    import jax
    lat, samples = [], 0
    i = cell.first_call
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    cpu0 = os.times()
    t0 = time.perf_counter()
    t_end = t0 + seconds
    with span("bench.window"):
        while True:
            with span("bench.feed"):
                args = cell.feed(i)
            t_call = time.perf_counter()
            with span("bench.call"):
                out = cell.call(args)
            with span("bench.wait"):
                jax.block_until_ready(out)
            t1 = time.perf_counter()
            lat.append(t1 - t_call)
            samples += cell.done(i, out)
            i += 1
            if t1 >= t_end:
                break
    window_s = t1 - t0
    cpu1 = os.times()
    if trace_dir:
        jax.profiler.stop_trace()
    return {"latencies": lat, "samples": samples, "calls": i - cell.first_call,
            "window_s": window_s, "cpu_user_s": cpu1.user - cpu0.user,
            "cpu_system_s": cpu1.system - cpu0.system}


def host_load() -> str:
    """The host's one-minute load average, where the system reports it."""
    try:
        return f"{os.getloadavg()[0]:.2f}"
    except OSError:
        return "unknown"


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks)


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every compared number against its limit (a number at or under its
    limit passes); a number without a limit fails."""
    checks, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        good = limit is not None and value == value and value <= limit
        ok &= good
        checks[name] = {"value": value, "limit": limit}
    return ok, checks


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True, overrides: dict | None = None) -> dict:
    """Run one cell once and return its result object.

    ``require_chip=False`` and ``overrides`` (smaller configuration and
    traffic) let the tests drive a whole run on the CPU."""
    cell = resolve(workload, overrides)
    import jax
    setup_jax(require_chip)
    chips = int(cell["workload"]["chips"])
    if require_chip:
        devices = chips_or_fail(chips)
    else:
        devices = jax.devices()[:chips]

    from bench import clock as clk
    compiles = clk.CompileClock()
    ctx = Context(cell, seed, devices)
    kind = load_module(os.path.join(BENCH, "kinds",
                                    ctx.traffic["kind"] + ".py"))
    driver = load_module(os.path.join(BENCH, "drivers",
                                      ctx.traffic["driver"] + ".py"))
    with span("bench.setup.build"):
        program = driver.build(ctx)
    ctx.g0 = None               # the program holds its own copy now
    with span("bench.setup.warm"):
        loop = kind.Loop(ctx, program)
    del program                 # the loop holds it until `finish`
    from repro.sim import compiled as csim
    traces_before = sum(csim.trace_counts().values())
    before = compiles.snapshot()
    # what set-up left alive (JAX's modules, the compiled programs, the
    # pools) is not rescanned by every full collection in the window: such
    # a collection paused a step by about 0.1 s once or twice a window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START

    trace_dir = None
    if trace:
        trace_dir = os.path.join(TRACE_DIR, workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    win = window(loop, seconds, trace_dir)
    after = compiles.snapshot()
    retraces = sum(csim.trace_counts().values()) - traces_before
    peak = memory_peak(devices)
    state = loop.finish()               # reads the program, then frees it
    numbers = {**loop.check(), **state}
    correct, checks = judge(numbers, cell["limits"])

    lines = [f"compiles in window: {after['compiles'] - before['compiles']}"
             f" (retraces {retraces}), set-up compiles {before['compiles']}"
             f" ({before['compile_s']:.3f} s), persistent cache hits "
             f"{before['cache_hits']}/{before['cache_requests']}",
             f"window: {win['calls']} calls, {win['samples']} samples, "
             f"{win['window_s']:.6f} s",
             "per-call latency ms: p50 {:.4f}, p95 {:.4f}, max {:.4f}"
             .format(*(1e3 * clk.percentile(win["latencies"], p)
                       for p in (50, 95, 100))),
             f"window cpu: user {win['cpu_user_s']:.3f} s, system "
             f"{win['cpu_system_s']:.3f} s; host load {host_load()} on "
             f"{os.cpu_count()} cpus"]
    dev0 = devices[0]
    result = {"correct": bool(correct),
              "attempted": win["samples"], "failed": 0}
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        from bench import trace as tr
        red = tr.reduce(tr.find_xplane(trace_dir),
                        [d.id for d in devices])
        ctx_m = Reading(ctx, win, red, loop.PASSES)
        metrics = {}
        for m in cell["per_layer"]:
            reader = load_module(os.path.join(BENCH, "metrics",
                                              m["name"] + ".py"))
            value = reader.read(ctx_m)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = red.busy_s()
        device["window_s"] = red.window_s
        result["breakdown"] = tr.breakdown(red)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {"setup_s": setup_s,
                  **loop.end_to_end(win)}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    result["_lines"] = lines
    return result


class Reading:
    """What a per-layer metric reader sees: the cell, the reduced trace,
    the calls and samples of the traced window, the loop's passes over the
    network per sample, the peaks and the work."""

    def __init__(self, ctx, win, red, passes: int):
        from bench import work
        self.cfg, self.traffic, self.chips = ctx.cfg, ctx.traffic, ctx.chips
        self.passes = passes
        self.calls, self.samples = win["calls"], win["samples"]
        self.red = red
        self.work = work
        self.peak = work.peak(ctx.devices[0].device_kind)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    try:
        res = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 3
    for line in res.pop("_lines"):
        print(line, file=sys.stderr)
    for name, c in res["checks"].items():
        verdict = "ok" if c["limit"] is not None and c["value"] <= c["limit"] \
            else "FAIL"
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
