#!/usr/bin/env python
"""Run the simulator's main path once on a TPU and check it against the
plain reference.

  python chip_smoke.py               # one chip: virtual chip, pipeline, farm
  python chip_smoke.py --four-chips  # a farm sharded over four chips only

One process drives every phase through the objects the CLIs build
(`launch/chipsim.build_chip`, `sim.fabric.build_pipeline`,
`sim.cluster.build_farm`), at the published widths of the paper's networks
(`configs/paper_apps.py`), with conductances drawn from ``--seed``:

  * virtual chip, ``mnist_class`` (13 cores): ``infer`` and
    ``infer_stream`` over 1024 samples, 4 ``train_step``s at batch 64, and
    the 1% cross-validation of the counters against ``core/hw_model.py``;
  * pipeline, ``isolet_class`` (160 cores, more than one 144-core chip)
    split over 2 modelled chips: 256 served requests, then 2 train steps at
    batch 16, checked against the serial ``VirtualChip`` as
    ``launch/pipeline.py --check-serial`` does;
  * farm, ``kdd_anomaly``, 4 virtual chips on one device: 256 served
    requests, then 2 reconciled train steps at batch 64;
  * the compiled scan's jnp body against its Pallas kernel body on the same
    chip (``REPRO_SIM_FORCE_KERNELS``), for the chip and the farm.

Every phase compares its outputs, and its conductances after training,
with ``core.crossbar.mlp_forward`` / ``paper_backprop_step``.  With
``--four-chips`` only the farm sharded over four devices
(``make_farm_mesh(4)``) runs, against the same farm on one device.

The script checks for a TPU before any work and exits non-zero without a
result when JAX finds none; it never falls back to interpret mode.  Its
last line is ``{"ok": true, "device": {...}}`` as JAX reports the device.
Timings are one run's host wall clock around work that ends in
``block_until_ready``; they are not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from bench.clock import CompileClock

REPO = os.path.dirname(os.path.abspath(__file__))
LR = 0.1

# --- Tolerances -----------------------------------------------------------
# The simulator and the reference compute the same f32 numerics: every
# contraction runs at Precision.HIGHEST (core.crossbar.F32), so the TPU's
# one-pass bf16 default does not apply.  What still differs is the order
# of summation: per-core tiles, a sequential sum over fan-in tiles and the
# MXU's accumulation against one XLA dot.  That moves a dot product by a
# few float32 ulps, far below OUT_TOL; the repo's CPU pins use the same.
# Outputs and output errors must agree to OUT_TOL everywhere: a 3-bit ADC
# code flipped upstream would move them by far more.
OUT_TOL = 1e-5      # activation units
# A pulse count round(dw / unit) whose dw sits within an ulp of a half
# unit can land one unit away (unit = max_update / update_levels; each
# device of the pair then moves by unit / 2).  Conductances may take such
# flips on at most FLIP_SHARE of their entries, one per weight per step
# (`g_tol`); a systematic error moves far more entries, or by more.
FLIP_SHARE = 1e-3
XVAL_LIMIT = 0.01   # the counters' cross-validation contract (1%)


def g_tol(spec, steps: int) -> float:
    """Conductance tolerance after ``steps`` updates: one pulse-count flip
    per step, plus float noise."""
    return steps * 0.5 * spec.max_update / spec.update_levels + OUT_TOL


def require_tpu():
    """Return ``jax.devices()`` or exit non-zero when they are not TPUs."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); nothing was run")
    return devices


class Phase:
    """Timing, deviations and checks of one phase, printed as it goes."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock
        self.failures: list[str] = []

    def say(self, msg: str) -> None:
        """Print one line tagged with the phase name."""
        print(f"[{self.name}] {msg}", flush=True)

    def timed(self, label: str, fn, *args, **kw):
        """Run ``fn`` to completion; print its wall time and the compile
        time JAX spent inside it."""
        import jax
        before = self.clock.snapshot()
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args, **kw))
        wall = time.perf_counter() - t0
        after = self.clock.snapshot()
        comp = after["compile_s"] - before["compile_s"]
        trace = after["trace_lower_s"] - before["trace_lower_s"]
        self.say(f"time {label}: {wall:.4f} s wall, of which compile "
                 f"{comp:.3f} s and trace+lower {trace:.3f} s")
        return out

    def check(self, label: str, dev: tuple[float, float],
              tol: float) -> None:
        """Record one (max deviation, share of entries off by more than
        ``OUT_TOL``) against its tolerance."""
        worst, share = dev
        ok = worst <= tol and share <= FLIP_SHARE
        self.say(f"dev {label}: max {worst!r} (tol {tol:g}), share off by "
                 f"more than {OUT_TOL:g}: {share:.2e} (limit "
                 f"{FLIP_SHARE:g}) {'ok' if ok else 'FAIL'}")
        if not ok:
            self.failures.append(label)


def deviation(a, b) -> tuple[float, float]:
    """(max |a - b|, share of entries with |a - b| > OUT_TOL)."""
    import numpy as np
    d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    return float(d.max()), float((d > OUT_TOL).mean())


def layers_deviation(la, lb) -> tuple[float, float]:
    """Conductance deviation over every layer and both devices of a pair."""
    import numpy as np
    d = np.concatenate([
        np.abs(np.asarray(a[k], np.float64)
               - np.asarray(b[k], np.float64)).ravel()
        for a, b in zip(la, lb) for k in ("g_plus", "g_minus")])
    return float(d.max()), float((d > OUT_TOL).mean())


def copy_layers(layers):
    """Fresh buffers: the simulator donates its own stacks."""
    import jax.numpy as jnp
    return [{k: jnp.array(v) for k, v in p.items()} for p in layers]


def draw(seed: int, tag: int, shape):
    """Uniform [-0.5, 0.5) data, the CLIs' input and target range."""
    import jax
    return jax.random.uniform(
        jax.random.fold_in(jax.random.PRNGKey(seed + 1), tag), shape,
        minval=-0.5, maxval=0.5)


def check_xval(ph: Phase, cmp_: dict) -> None:
    """The counters' 1% cross-validation against ``hw_model``."""
    worst = max(cmp_.values())
    ok = worst <= XVAL_LIMIT
    ph.say("cross-validation (rel err): "
           + " ".join(f"{k}={v:.2e}" for k, v in sorted(cmp_.items()))
           + f" -> max {worst:.2e} (limit {XVAL_LIMIT:g}) "
           + ("ok" if ok else "FAIL"))
    if not ok:
        ph.failures.append("cross-validation")


def body_flags(ph: Phase) -> None:
    """Which scan body and kernel mode this phase compiles."""
    from repro.kernels import ops as kernel_ops
    from repro.sim import compiled as csim
    ph.say(f"use_kernels={csim.kernel_body_enabled()} "
           f"interpret={kernel_ops._default_interpret()}")


def train_against_reference(ph: Phase, step_fn, ref_layers, spec, *,
                            steps: int, batch: int, dims, seed: int,
                            tag0: int):
    """Run ``steps`` train steps through ``step_fn(label, x, t)`` and the
    same steps through ``paper_backprop_step``; check each step's error."""
    from repro.core import crossbar as xb
    for step in range(steps):
        x = draw(seed, tag0 + step, (batch, dims[0]))
        t = draw(seed, tag0 + 100 + step, (batch, dims[-1]))
        err = step_fn(f"train step {step} batch {batch}", x, t)
        ref_layers, ref_err = xb.paper_backprop_step(ref_layers, x, t,
                                                     spec, LR)
        ph.check(f"train step {step} error vs paper_backprop_step",
                 deviation(err, ref_err), OUT_TOL)
    return ref_layers


def phase_chip(clock, seed: int, *, n_infer=1024, steps=4, batch=64):
    """Virtual chip, mnist_class: recognition, training, cross-validation."""
    from repro.configs.paper_apps import NETWORKS, PAPER_SPEC
    from repro.core import crossbar as xb, hw_model as hw
    from repro.launch.chipsim import build_chip

    ph = Phase("chip mnist_class", clock)
    dims = NETWORKS["mnist_class"]
    chip = build_chip("mnist_class", seed=seed)
    body_flags(ph)
    ph.say(f"{dims}: {chip.placement.n_cores} cores, "
           f"{len(chip.placement.stages)} stages")
    ref_layers = copy_layers(chip.layers())
    x = draw(seed, 0, (n_infer, dims[0]))
    ref = xb.mlp_forward(ref_layers, x, PAPER_SPEC)
    out = ph.timed(f"infer {n_infer}", chip.infer, x)
    ph.check("infer vs mlp_forward", deviation(out, ref), OUT_TOL)
    out_s, _ = ph.timed(f"infer_stream {n_infer}", chip.infer_stream, x)
    ph.check("infer_stream vs mlp_forward", deviation(out_s, ref), OUT_TOL)
    ref_layers = train_against_reference(
        ph, lambda label, a, b: ph.timed(label, chip.train_step, a, b,
                                         lr=LR), ref_layers,
        PAPER_SPEC, steps=steps, batch=batch, dims=dims, seed=seed,
        tag0=10)
    ph.check(f"conductances after {steps} steps",
             layers_deviation(chip.layers(), ref_layers),
             g_tol(PAPER_SPEC, steps))
    cost = hw.network_cost("mnist_class", dims)
    check_xval(ph, chip.report().compare_hw(cost))
    return ph


def phase_pipeline(clock, seed: int, *, n_requests=256, steps=2, batch=16):
    """Pipeline fabric, isolet_class over 2 chips: serving and training,
    checked against the serial chip and the reference."""
    from repro.configs.paper_apps import NETWORKS, PAPER_SPEC
    from repro.core import crossbar as xb
    from repro.sim.chip import VirtualChip
    from repro.sim.fabric import build_pipeline

    ph = Phase("pipeline isolet_class k2", clock)
    dims = NETWORKS["isolet_class"]
    pipe = build_pipeline("isolet_class", n_chips=2, seed=seed)
    body_flags(ph)
    ph.say(f"{dims}: {pipe.placement.n_cores} cores over {pipe.n_chips} "
           f"chips {[c.placement.n_cores for c in pipe.chips]}")
    serial = VirtualChip(copy_layers(pipe.layers()), PAPER_SPEC,
                         name="isolet_class")
    ref_layers = copy_layers(pipe.layers())
    x = draw(seed, 1, (n_requests, dims[0]))
    ref = xb.mlp_forward(ref_layers, x, PAPER_SPEC)
    out, _ = ph.timed(f"serve {n_requests}", pipe.serve, x)
    ph.check("serve vs mlp_forward", deviation(out, ref), OUT_TOL)

    def step(label, a, b):
        err = ph.timed(label, pipe.train_step, a, b, lr=LR)
        err_s = ph.timed(f"{label} (serial chip)", serial.train_step, a, b,
                         lr=LR)
        ph.check("train error vs serial chip", deviation(err, err_s), OUT_TOL)
        return err

    ref_layers = train_against_reference(
        ph, step, ref_layers, PAPER_SPEC, steps=steps, batch=batch,
        dims=dims, seed=seed, tag0=20)
    ph.check(f"conductances vs serial chip after {steps} steps",
             layers_deviation(pipe.layers(), serial.layers()),
             g_tol(PAPER_SPEC, steps))
    ph.check(f"conductances vs paper_backprop_step after {steps} steps",
             layers_deviation(pipe.layers(), ref_layers),
             g_tol(PAPER_SPEC, steps))
    check_xval(ph, pipe.report().compare_hw())
    return ph


def phase_farm(clock, seed: int, *, n_chips=4, n_requests=256, steps=2,
               batch=64):
    """Chip farm, kdd_anomaly, virtual chips on ONE device (mesh=None):
    serving and reconciled data-parallel training."""
    from repro.configs.paper_apps import NETWORKS, PAPER_SPEC
    from repro.core import crossbar as xb
    from repro.sim.cluster import build_farm

    ph = Phase(f"farm kdd_anomaly c{n_chips}", clock)
    dims = NETWORKS["kdd_anomaly"]
    farm = build_farm("kdd_anomaly", n_chips, seed=seed, mesh=None)
    body_flags(ph)
    ref_layers = copy_layers(farm.layers())
    x = draw(seed, 2, (n_requests, dims[0]))
    ref = xb.mlp_forward(ref_layers, x, PAPER_SPEC)
    out, _ = ph.timed(f"serve {n_requests}", farm.serve, x)
    ph.check("serve vs mlp_forward", deviation(out, ref), OUT_TOL)
    ref_layers = train_against_reference(
        ph, lambda label, a, b: ph.timed(label, farm.train_step, a, b,
                                         lr=LR, reconcile="none"),
        ref_layers, PAPER_SPEC, steps=steps, batch=batch, dims=dims,
        seed=seed, tag0=30)
    ph.check(f"conductances after {steps} steps",
             layers_deviation(farm.layers(), ref_layers),
             g_tol(PAPER_SPEC, steps))
    if not farm.replicas_in_sync():
        ph.say("replicas out of sync FAIL")
        ph.failures.append("replicas in sync")
    rep = farm.report()
    check_xval(ph, {**rep.compare_chip_sum(), **rep.compare_hw()})
    return ph


def phase_bodies(clock, seed: int, *, n_infer=1024, batch=64, repeats=5,
                 n_requests=256):
    """The compiled scan's jnp body against its Pallas kernel body, on the
    same chip and data: per-call wall time after warm-up, and agreement."""
    import jax
    from repro.configs.paper_apps import NETWORKS
    from repro.launch.chipsim import build_chip
    from repro.sim.cluster import build_farm

    ph = Phase("bodies", clock)
    results = {}
    for body, flag in (("jnp", "0"), ("kernel", "1")):
        os.environ["REPRO_SIM_FORCE_KERNELS"] = flag
        try:
            dims = NETWORKS["mnist_class"]
            chip = build_chip("mnist_class", seed=seed)
            x = draw(seed, 0, (n_infer, dims[0]))
            xt = draw(seed, 40, (batch, dims[0]))
            tt = draw(seed, 140, (batch, dims[-1]))
            out = ph.timed(f"{body} chip infer {n_infer} first",
                           chip.infer, x, count=False)
            t0 = time.perf_counter()
            for _ in range(repeats):
                jax.block_until_ready(chip.infer(x, count=False))
            t_inf = (time.perf_counter() - t0) / repeats
            err = ph.timed(f"{body} chip train first", chip.train_step,
                           xt, tt, lr=LR)
            t0 = time.perf_counter()
            for _ in range(repeats):
                jax.block_until_ready(chip.train_step(xt, tt, lr=LR))
            t_tr = (time.perf_counter() - t0) / repeats

            fdims = NETWORKS["kdd_anomaly"]
            farm = build_farm("kdd_anomaly", 4, seed=seed, mesh=None)
            xr = draw(seed, 2, (n_requests, fdims[0]))
            served, _ = ph.timed(f"{body} farm serve {n_requests} first",
                                 farm.serve, xr)
            t0 = time.perf_counter()
            for _ in range(repeats):
                jax.block_until_ready(farm.serve(xr)[0])
            t_srv = (time.perf_counter() - t0) / repeats
        finally:
            os.environ.pop("REPRO_SIM_FORCE_KERNELS", None)
        ph.say(f"{body} body steady per call: chip infer {n_infer} "
               f"{t_inf * 1e3:.3f} ms, chip train batch {batch} "
               f"{t_tr * 1e3:.3f} ms, farm serve {n_requests} "
               f"{t_srv * 1e3:.3f} ms")
        results[body] = (out, err, served)
    for i, what in enumerate(("chip infer", "chip first train error",
                              "farm serve")):
        ph.check(f"{what} jnp vs kernel body",
                 deviation(results["jnp"][i], results["kernel"][i]), OUT_TOL)
    return ph


def phase_four_chips(clock, seed: int, *, n_chips=4, batch=64, steps=2):
    """A kdd_anomaly farm sharded over four devices against the same farm
    on one device: ``infer`` and two train steps must agree."""
    import jax
    from repro.configs.paper_apps import NETWORKS, PAPER_SPEC
    from repro.sim.cluster import build_farm, make_farm_mesh

    ph = Phase(f"four-chip farm kdd_anomaly c{n_chips}", clock)
    if jax.device_count() < n_chips:
        ph.say(f"needs {n_chips} devices, found {jax.device_count()} FAIL")
        ph.failures.append("devices")
        return ph
    dims = NETWORKS["kdd_anomaly"]
    mesh = make_farm_mesh(n_chips)
    if mesh is None or mesh.shape["chips"] != n_chips:
        ph.say(f"make_farm_mesh({n_chips}) gave {mesh} FAIL")
        ph.failures.append("mesh")
        return ph
    body_flags(ph)
    ph.say(f"mesh {dict(mesh.shape)} over "
           f"{[str(d) for d in mesh.devices.flat]}")
    sharded = build_farm("kdd_anomaly", n_chips, seed=seed, mesh=mesh)
    single = build_farm("kdd_anomaly", n_chips, seed=seed, mesh=None)
    x = draw(seed, 3, (batch, dims[0]))
    y_sh = ph.timed(f"sharded infer {batch}", sharded.infer, x)
    y_one = ph.timed(f"one-device infer {batch}", single.infer, x)
    ph.check("infer sharded vs one device", deviation(y_sh, y_one), OUT_TOL)
    for step in range(steps):
        xt = draw(seed, 50 + step, (batch, dims[0]))
        tt = draw(seed, 150 + step, (batch, dims[-1]))
        e_sh = ph.timed(f"sharded train step {step}", sharded.train_step,
                        xt, tt, lr=LR)
        e_one = ph.timed(f"one-device train step {step}", single.train_step,
                         xt, tt, lr=LR)
        ph.check(f"train step {step} error sharded vs one device",
                 deviation(e_sh, e_one), OUT_TOL)
    ph.check(f"conductances sharded vs one device after {steps} steps",
             layers_deviation(sharded.layers(), single.layers()),
             g_tol(PAPER_SPEC, steps))
    for si, g in enumerate(sharded._gp):
        where = sorted((s.index[0].start or 0, str(s.device))
                       for s in g.addressable_shards)
        ph.say(f"stage {si} conductance stack {tuple(g.shape)}: "
               + ", ".join(f"chips from {c} on {d}" for c, d in where))
    ph.say("sharded infer output on "
           f"{sorted(str(s.device) for s in y_sh.addressable_shards)}")
    if len({s.device for g in sharded._gp
            for s in g.addressable_shards}) < n_chips:
        ph.say("conductance stacks do not span every device FAIL")
        ph.failures.append("placement")
    if not sharded.replicas_in_sync():
        ph.say("replicas out of sync FAIL")
        ph.failures.append("replicas in sync")
    return ph


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the farm sharded over four devices and "
                         "its one-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_tpu()
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    dev0 = devices[0]
    print(f"device: {dev0.platform} {dev0.device_kind} x{len(devices)}; "
          f"compile cache {cache}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.four_chips:
        phases = [phase_four_chips]
    else:
        phases = [phase_chip, phase_pipeline, phase_farm, phase_bodies]
    failed = []
    for fn in phases:
        ph = fn(clock, args.seed)
        failed += [f"{ph.name}: {f}" for f in ph.failures]
        if failed:
            break
    tot = clock.snapshot()
    print(f"total: {time.perf_counter() - t0:.2f} s wall, backend compile "
          f"{tot['compile_s']:.2f} s, trace+lower {tot['trace_lower_s']:.2f}"
          f" s, persistent cache hits {tot['cache_hits']}/"
          f"{tot['cache_requests']}", flush=True)
    if failed:
        print("FAILED: " + "; ".join(failed), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
