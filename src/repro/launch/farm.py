"""Chip-farm CLI: serve and train a paper application on N virtual chips.

  PYTHONPATH=src python -m repro.launch.farm --app kdd_anomaly --chips 4
  PYTHONPATH=src python -m repro.launch.farm --app mnist_class --chips 2 \\
      --requests 16 --train-steps 2 --batch 8
  PYTHONPATH=src python -m repro.launch.farm --app kdd_anomaly --chips 2 \\
      --reconcile int8 --json farm.json

Builds a data-parallel farm of N chip replicas (repro.sim.cluster), routes
a request queue through the pipelined serving front-end (one chip-axis
stacked Pallas call per beat across the whole farm), runs reconciled
data-parallel training steps, and prints aggregate throughput / energy
from the *measured* counters — cross-validated against the summed
per-chip counters and `hw_model.farm_cost` (DESIGN.md §6).  With more
than one JAX device the chip axis is shard_mapped over a ``("chips",)``
mesh; pass ``--no-mesh`` to force single-device execution.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from repro.configs.paper_apps import NETWORKS, PAPER_SPEC
from repro.core import crossbar as xb, hw_model as hw
from repro.launch.compile_cache import enable_compile_cache
from repro.sim.cluster import build_farm, make_farm_mesh


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--app", default="kdd_anomaly", choices=sorted(NETWORKS))
    ap.add_argument("--chips", type=int, default=2)
    ap.add_argument("--requests", type=int, default=8,
                    help="serving requests routed through the farm")
    ap.add_argument("--train-steps", type=int, default=1)
    ap.add_argument("--batch", type=int, default=None,
                    help="global batch per training step "
                         "(default: one sample per chip)")
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--share-small-layers", action="store_true")
    ap.add_argument("--reconcile", default="none", choices=["none", "int8"],
                    help="host-link update reconciliation numerics: exact "
                         "f32 sum (== serial chip) or 8-bit sign-magnitude "
                         "codes (matches the metered 8-bit wire format, "
                         "bounded deviation); accounting meters 8-bit "
                         "codes either way")
    ap.add_argument("--no-mesh", action="store_true",
                    help="keep the chip axis on one device even when "
                         "multiple JAX devices exist")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    mesh = None if args.no_mesh else make_farm_mesh(args.chips)
    farm = build_farm(args.app, args.chips, seed=args.seed,
                      share_small_layers=args.share_small_layers, mesh=mesh)
    dims = NETWORKS[args.app]
    batch = args.batch if args.batch is not None else args.chips
    print(f"== {args.app}: {dims} on a {args.chips}-chip farm "
          f"({farm.placement.n_cores} cores/chip, "
          f"mesh={'yes' if mesh is not None else 'no'}) ==")

    key = jax.random.PRNGKey(args.seed + 1)
    if args.requests > 0:
        x = jax.random.uniform(key, (args.requests, dims[0]),
                               minval=-0.5, maxval=0.5)
        out, stats = farm.serve(x)
        ref = xb.mlp_forward(farm.layers(), x, PAPER_SPEC)
        dev = float(jnp.abs(out - ref).max())
        print(f" serve: {args.requests} requests in {stats['beats']} beats "
              f"(beat {stats['beat_us']:.2f} us) -> "
              f"{stats['samples_per_s']:.0f} samples/s steady-state, "
              f"max dev vs mlp_forward {dev:.2e}")

    for step in range(args.train_steps):
        xb_ = jax.random.uniform(jax.random.fold_in(key, 10 + step),
                                 (batch, dims[0]), minval=-0.5, maxval=0.5)
        tgt = jax.random.uniform(jax.random.fold_in(key, 50 + step),
                                 (batch, dims[-1]), minval=-0.5, maxval=0.5)
        err = farm.train_step(xb_, tgt, lr=args.lr,
                              reconcile=args.reconcile)
        print(f" train step {step}: |err| {float(jnp.abs(err).mean()):.4f} "
              f"(replicas in sync: {farm.replicas_in_sync()})")

    rep = farm.report()
    cost = hw.farm_cost(args.app, dims, args.chips,
                        batch_per_chip=max(batch // args.chips, 1),
                        share_small_layers=args.share_small_layers)
    print(f" measured: serve {rep.serve_samples_per_s:.0f} samples/s "
          f"@ {rep.serve_j_per_sample * 1e12:.1f} pJ/sample "
          f"(host link util {rep.host_link_utilization:.3f}); "
          f"train step {rep.train_step_us:.2f} us "
          f"@ {rep.train_j_per_sample * 1e12:.1f} pJ/sample")
    chip_sum = rep.compare_chip_sum()
    cmp_ = rep.compare_hw(cost)
    print(" vs summed per-chip counters: "
          + " ".join(f"{k}={v:.2e}" for k, v in chip_sum.items()))
    print(" cross-validation vs farm_cost (rel err): "
          + " ".join(f"{k}={v:.2e}" for k, v in cmp_.items()))
    if rep.serve_samples:
        g_infer = hw.gpu_cost(list(dims), train=False)
        print(f" vs K20 (measured): "
              f"{g_infer.time_us * rep.serve_samples_per_s / 1e6:.1f}x "
              f"serve throughput, "
              f"{g_infer.energy_j / rep.serve_j_per_sample:.0f}x "
              f"energy/sample")
    bad = {k: v for k, v in {**chip_sum, **cmp_}.items() if v > 0.01}
    if bad:
        raise SystemExit(f"farm cross-validation FAILED (>1%): {bad}")

    if args.json:
        record = {"app": args.app, "chips": args.chips, "dims": dims,
                  "rows": rep.rows(), "chip_sum": chip_sum,
                  "cross_validation": cmp_}
        with open(args.json, "w") as f:
            json.dump(record, f, indent=2)
        print(f"# wrote {args.json}")


if __name__ == "__main__":
    main()
