"""Benchmark harness: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run table2 fig21   # subset

Each row prints ``name,us_per_call,derived`` CSV.  Suites listed in
``JSON_SUITES`` additionally write their rows to ``BENCH_<key>.json`` in
the repo root so the perf trajectory is tracked across PRs (CI uploads
them as artifacts).
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

# suites whose rows are persisted as BENCH_<key>.json
JSON_SUITES = ("kernels", "sim", "farm", "pipeline")

BENCHES = {
    "table2": "benchmarks.bench_core_model",        # Table II
    "tables34": "benchmarks.bench_system_vs_gpu",   # Tables III/IV, Figs 22-25
    "fig16": "benchmarks.bench_training_curves",    # Fig 16 + VI.B
    "fig21": "benchmarks.bench_constraints",        # Fig 21
    "anomaly": "benchmarks.bench_anomaly",          # Figs 18-20
    "cluster": "benchmarks.bench_clustering",       # section IV.B core
    "kernels": "benchmarks.bench_kernels",          # Pallas kernels
    "sim": "benchmarks.bench_chip_sim",             # virtual chip (repro.sim)
    "farm": "benchmarks.bench_farm",                # chip farm (sim.cluster)
    "pipeline": "benchmarks.bench_pipeline",        # pipeline fabric (sim.fabric)
    "lm": "benchmarks.bench_lm_step",               # framework LM steps
    "dryrun": "benchmarks.bench_dryrun_table",      # §Roofline cells (cached)
}


def _emit_json(key: str, rows: list[dict], elapsed_s: float) -> None:
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), f"BENCH_{key}.json")
    record = {"suite": key, "backend": None, "elapsed_s": round(elapsed_s, 2),
              "rows": rows}
    try:
        import jax
        record["backend"] = jax.default_backend()
    except Exception:
        pass
    with open(out, "w") as f:
        json.dump(record, f, indent=2)
    print(f"# wrote {out} ({len(rows)} rows)", flush=True)


def main() -> None:
    from benchmarks import common
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    wanted = sys.argv[1:] or list(BENCHES)
    failures = []
    for key in wanted:
        mod_name = BENCHES[key]
        print(f"# === {key} ({mod_name}) ===", flush=True)
        t0 = time.time()
        common.drain_rows()
        try:
            mod = __import__(mod_name, fromlist=["main"])
            mod.main()
        except Exception:
            traceback.print_exc()
            failures.append(key)
        elapsed = time.time() - t0
        rows = common.drain_rows()
        if key in JSON_SUITES and key not in failures:
            # never overwrite a complete record with a crashed suite's
            # partial rows — the trajectory tracking would read it as a
            # valid (fewer-row) result
            _emit_json(key, rows, elapsed)
        print(f"# {key} done in {elapsed:.1f}s", flush=True)
    if failures:
        print(f"# FAILED: {failures}")
        sys.exit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
