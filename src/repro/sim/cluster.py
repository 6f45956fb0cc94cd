"""Chip farm: N virtual chips under one host (DESIGN.md §6).

The single-chip simulator (`repro.sim.chip`) executes one placed network;
the farm scales it out the way the ROADMAP's serving story requires:

  * ``ChipFarm`` — N data-parallel chip replicas.  Every stage's stacked
    conductances carry a leading *chip* axis ``(C, T, rows, cols)``, and
    every stage of every chip executes as ONE chip-axis stacked Pallas call
    (`kernels/ops.crossbar_*_stacked` with 4-D operands) — the farm is a
    single fused dispatch per phase, never a Python loop over chips.

  * data-parallel training — each chip runs the paper's fwd/bwd phases on
    its batch shard, computes its LOCAL batch-summed outer product
    (`crossbar_dw_stacked`), and the host link reconciles:
    ``dist.collectives.farm_reduce_sum`` sums the contributions, the pulse
    discretization (III.F step 3) is applied ONCE to the sum, and every
    replica writes the same pulses.  Two consequences, both pinned by
    ``tests/test_farm.py``:
      - replicas stay bitwise in lockstep (no drift to re-sync), and
      - the farm equals a serial `VirtualChip.train_step` on the unsharded
        batch, because (a) stages are sample-independent, (b) the error
        full-scale is shared farm-wide (the 8-bit error ADC quantizes the
        *global* delta tensor — a `farm_max` collective in the distributed
        view), and (c) summed local outer products == the global one.

  * ``FarmServer`` — the batched serving front-end: a
    `runtime.serve_loop.RequestQueue` with per-slot refill feeds each
    chip's stage-0 slot every pipeline beat; all stages of all chips
    evaluate in one chip-axis stacked call per beat (plus one aggregation
    call when fan-in-split stages exist), and each beat retires one
    sample per chip at steady state — Table IV's 0.77 us beat, times N.

  * accounting — per-chip `PhaseCounters` (identical conventions to the
    single chip, so the §5.3 contract holds per replica) plus a
    `HostLinkTracker` for sample ingress/egress and update-reconciliation
    traffic; `ChipFarm.report()` aggregates them into a `FarmReport`
    cross-validated against `hw_model.farm_cost`.

With a JAX device mesh (``mesh=`` with a ``"chips"`` axis), the chip-axis
dispatches run under ``shard_map`` — each device executes its chip slice
of the same stacked call; reconciliation happens on the gathered
contributions (parameter-server discipline).  Without a mesh the same
code runs single-device (the chip axis is just an array axis).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hw_model as hw
from repro.core import quantization as q
from repro.core.crossbar import (CORE_COLS, CORE_ROWS, CrossbarSpec,
                                 hard_sigmoid, hard_sigmoid_deriv)
from repro.core.mapping import map_network
from repro.kernels import ops as kernel_ops
from repro.runtime.serve_loop import RequestQueue
from repro.sim import compiled as csim
from repro.sim import spans
from repro.sim.chip import VirtualChip, _tile_cols, compiled_enabled
from repro.sim.noc import NocTracker
from repro.sim.placer import (Placement, StageStacks, build_stage_stacks,
                              fold_subneuron_partials, place_network,
                              stage_dot_products, stage_dp_from_outputs,
                              tile_inputs)
from repro.sim.report import (FarmReport, HostLinkTracker, PhaseCounters,
                              SimReport)


def make_farm_mesh(n_chips: int):
    """A ``("chips",)`` mesh over the largest divisor of ``n_chips`` that
    fits the local devices (shard_map needs the chip axis to divide the
    mesh), or None when that divisor is 1 — the chip axis then stays a
    plain array axis on one device."""
    n_dev = jax.local_device_count()
    span = next((d for d in range(min(n_chips, n_dev), 1, -1)
                 if n_chips % d == 0), 1)
    if span == 1:
        return None
    # Auto axes: the eager chip-axis ops between shard_mapped dispatches
    # run outside any mesh context, which Explicit axes would reject
    return jax.make_mesh((span,), ("chips",),
                         axis_types=(jax.sharding.AxisType.Auto,))


class ChipFarm:
    """N data-parallel chip replicas executing as chip-axis stacked calls."""

    def __init__(self, layers: list[dict[str, jax.Array]],
                 spec: CrossbarSpec | None = None, *,
                 n_chips: int = 2,
                 rows: int = CORE_ROWS, cols: int = CORE_COLS,
                 name: str = "farm", share_small_layers: bool = False,
                 input_bits: int = 8, mesh=None):
        if spec is None:
            from repro.configs.paper_apps import PAPER_SPEC
            spec = PAPER_SPEC
        if spec.split_activation:
            raise NotImplementedError(
                "the farm inherits the virtual chip's exact-aggregation "
                "restriction (split_activation=False)")
        if n_chips < 1:
            raise ValueError(f"n_chips must be >= 1, got {n_chips}")
        self.spec = spec
        self.name = name
        self.n_chips = n_chips
        self.input_bits = input_bits
        self.share_small_layers = share_small_layers
        self.mesh = mesh
        self.version = 0            # bumped on every conductance write
        dims = [int(layers[0]["g_plus"].shape[0])] + \
               [int(p["g_plus"].shape[1]) for p in layers]
        nmap = map_network(dims, rows, cols,
                           share_small_layers=share_small_layers)
        self.placement: Placement = place_network(layers, nmap, rows, cols)
        # replicate every stage's stacks along the leading chip axis
        C = n_chips
        self._gp = [jnp.repeat(st.g_plus[None], C, axis=0)
                    for st in self.placement.stages]
        self._gm = [jnp.repeat(st.g_minus[None], C, axis=0)
                    for st in self.placement.stages]
        if mesh is not None:
            # each replica's stacks live on the device that runs its chip
            from jax.sharding import NamedSharding, PartitionSpec as P
            shard = NamedSharding(mesh, P("chips"))
            self._gp = [jax.device_put(g, shard) for g in self._gp]
            self._gm = [jax.device_put(g, shard) for g in self._gm]
        self.chip_infer = [PhaseCounters(
            noc=NocTracker(slot_cycles=self.placement.cols))
            for _ in range(C)]
        self.chip_train = [PhaseCounters(
            noc=NocTracker(slot_cycles=self.placement.cols))
            for _ in range(C)]
        self.serve_link = HostLinkTracker()
        self.train_link = HostLinkTracker()
        self.serve_beats = 0
        self.serve_sessions = 0          # each session pays one fill/drain
        # capacity is measured over FULL beats only (every chip retired):
        # a ragged request count leaves trailing slots idle, which is a
        # measurement artifact, not reduced farm capacity
        self.serve_full_beats = 0
        self.serve_full_samples = 0
        self.serve_full_requests = 0
        self.train_steps = 0
        self._stacks: StageStacks | None = None   # compiled-path layout
        self._fgp = self._fgm = None              # (S, C, T_max, R, cols)
        self._stacks_version = -1

    # ------------------------------------------------------------------
    # Compiled whole-step executor (repro.sim.compiled, DESIGN.md §8)
    # ------------------------------------------------------------------

    def _compiled_active(self) -> bool:
        """The compiled farm step runs the chip axis as an array axis on
        one device; the shard_mapped mesh path stays on the eager
        dispatches (its per-device placement is a different execution
        contract)."""
        return compiled_enabled() and self.mesh is None

    def _get_stacks(self):
        """(layout, gp (S, C, T_max, rows, cols), gm) — padded chip-axis
        stacks, rebuilt when the conductance version moved outside the
        compiled step."""
        if self._stacks is None or self._stacks_version != self.version:
            st = self._stacks = build_stage_stacks(self.placement)
            C = self.n_chips
            gp = jnp.zeros((st.S, C, st.T_max, st.rows, st.cols),
                           jnp.float32)
            gm = jnp.zeros_like(gp)
            for s in range(st.S):
                T = self._gp[s].shape[1]
                gp = gp.at[s, :, :T].set(self._gp[s])
                gm = gm.at[s, :, :T].set(self._gm[s])
            self._fgp, self._fgm = gp, gm
            self._stacks_version = self.version
        return self._stacks, self._fgp, self._fgm

    def _scatter_back(self, gp, gm, views_plus, views_minus) -> None:
        """Keep the compiled step's donated envelope and hand its
        per-stage chip-axis views (`compiled.kept_views`) to the
        per-stage lists that `extract_chip`/`layers` consumers read:
        plain assignment, no device work."""
        self._fgp, self._fgm = gp, gm
        self._gp, self._gm = list(views_plus), list(views_minus)
        self.version += 1
        self._stacks_version = self.version

    def _apply_phase_counters(self, counters: list[PhaseCounters],
                              fcnt, bcnt, Mc: int) -> None:
        """One host transfer of the scan's traced accumulators, fanned to
        every chip's `PhaseCounters` (replicas execute in lockstep, so
        the per-chip increments are identical), plus the static NoC
        replay."""
        st = self._stacks
        with spans.phase(spans.SYNC):
            f = [int(v) for v in np.asarray(fcnt)]
            b = ([int(v) for v in np.asarray(bcnt)] if bcnt is not None
                 else None)
        with spans.phase(spans.ACCOUNT):
            for c in counters:
                c.slots["fwd"] += f[0]
                c.core_steps["fwd"] += f[1]
                for s in range(st.S):
                    c.noc.record(self.placement.stages[s].index,
                                 st.routed[s], st.Ts[s], Mc)
                if b is not None:
                    c.slots["bwd"] += b[0]
                    c.core_steps["bwd"] += b[1]
                    c.slots["update"] += b[2]
                    c.core_steps["update"] += b[3]

    # ------------------------------------------------------------------
    # Chip-axis stacked dispatch (shard_mapped when a mesh is present)
    # ------------------------------------------------------------------

    def _shard(self, fn, n_in: int):
        if self.mesh is None:
            return fn
        from jax.sharding import PartitionSpec as P
        return jax.shard_map(fn, mesh=self.mesh,
                             in_specs=(P("chips"),) * n_in,
                             out_specs=P("chips"), check_vma=False)

    def _run_fwd(self, xs, gp, gm):
        return self._shard(
            lambda a, b, c: kernel_ops.crossbar_fwd_stacked(a, b, c), 3)(
            xs, gp, gm)

    def _run_bwd(self, dys, gp, gm):
        return self._shard(
            lambda a, b, c: kernel_ops.crossbar_bwd_stacked(a, b, c), 3)(
            dys, gp, gm)

    def _run_dw(self, xs, ds):
        return self._shard(
            lambda a, b: kernel_ops.crossbar_dw_stacked(a, b), 2)(xs, ds)

    # ------------------------------------------------------------------
    # Stage execution with a chip axis
    # ------------------------------------------------------------------

    def _stage_dp(self, si: int, h: jax.Array) -> jax.Array:
        """(C, Mc, fan_in) input wave -> (C, Mc, fan_out) dot products;
        the same `placer.stage_dot_products` the serial chip runs, with
        the chip-axis stacks and the (possibly shard_mapped) dispatch."""
        st = self.placement.stages[si]
        return stage_dot_products(st, h, self._gp[si], self._gm[si],
                                  self._run_fwd)

    def _count_stage(self, counters: list[PhaseCounters], st,
                     samples: int) -> None:
        links = st.g_plus.shape[0]
        for c in counters:
            c.record_phase("fwd", st.n_cores, samples)
            c.noc.record(st.index, st.lmap.routed_outputs, links, samples)

    def _forward(self, xb: jax.Array, counters: list[PhaseCounters] | None
                 ) -> tuple[list[jax.Array], list[jax.Array]]:
        """Chip-axis wave with the reference transport semantics."""
        acts, dps = [], []
        h = xb
        last = len(self.placement.stages) - 1
        for si, st in enumerate(self.placement.stages):
            acts.append(h)
            dp = self._stage_dp(si, h)
            dps.append(dp)
            if counters is not None:
                self._count_stage(counters, st, xb.shape[1])
            h = hard_sigmoid(dp)
            if si < last and self.spec.transport_quant:
                h = q.adc_quantize_ste(h, self.spec.adc_bits)
        return acts, dps

    def _split(self, x: jax.Array, what: str) -> jax.Array:
        x = jnp.atleast_2d(x)
        M = x.shape[0]
        if M % self.n_chips:
            raise ValueError(
                f"{what} batch {M} does not divide over {self.n_chips} "
                f"chips")
        return x.reshape(self.n_chips, M // self.n_chips, x.shape[1])

    # ------------------------------------------------------------------
    # Inference (wave semantics; serving goes through FarmServer)
    # ------------------------------------------------------------------

    @spans.entry("farm.infer")
    def infer(self, x: jax.Array, *, count: bool = True) -> jax.Array:
        """Data-parallel recognition wave: the global batch splits over
        chips, each replica computes its shard; rows come back in input
        order and equal `VirtualChip.infer` on the unsharded batch."""
        counters = self.chip_infer if count else None
        if self._compiled_active():
            with spans.phase(spans.PREPARE):
                xb = self._split(x, "infer")
                st, gp, gm = self._get_stacks()
                idx, cfg = st.index_pytree(), csim.chip_config(st, self.spec)
            with spans.phase(spans.DISPATCH):
                out, fcnt = csim.chip_infer(gp, gm, xb, idx, cfg)
            if count:
                self._apply_phase_counters(counters, fcnt, None, xb.shape[1])
        else:
            xb = self._split(x, "infer")
            _, dps = self._forward(xb, counters)
            out = hard_sigmoid(dps[-1])
        if count:
            with spans.phase(spans.ACCOUNT):
                Mc = xb.shape[1]
                bits = (self.placement.dims[0] * self.input_bits
                        + self.placement.dims[-1] * hw.ADC_BITS_OUT)
                for c in self.chip_infer:
                    c.samples += Mc
                    c.record_io(bits, Mc)
        with spans.phase(spans.WRITEBACK):
            return out.reshape(-1, out.shape[-1])

    # ------------------------------------------------------------------
    # Data-parallel training with reconciled pulse updates
    # ------------------------------------------------------------------

    @spans.entry("farm.train_step")
    def train_step(self, x: jax.Array, target: jax.Array, lr: float, *,
                   reconcile: str = "none") -> jax.Array:
        """One farm step on the global batch; equals the serial
        `VirtualChip.train_step` on the same data when ``reconcile`` is
        "none".  Mode "int8" codes each chip's contribution in the 8-bit
        wire format the link accounting already meters (bounded deviation
        from the serial chip); mode "none" idealizes an exact f32 sum over
        that same metered traffic.  Returns the (global) output error."""
        from repro.dist.collectives import farm_reduce_sum

        with spans.phase(spans.PREPARE):
            xb = self._split(x, "train")
            tb = self._split(jnp.atleast_2d(target), "target")
        spec = self.spec
        C, Mc = xb.shape[0], xb.shape[1]
        M = C * Mc

        if self._compiled_active():
            # the whole farm step — chip-axis wave, reversed bwd scan,
            # farm_reduce_sum reconciliation INSIDE the trace, pulses
            # broadcast to every replica — is ONE donated XLA program.
            with spans.phase(spans.PREPARE):
                st, gp, gm = self._get_stacks()
                idx, cfg = st.index_pytree(), csim.chip_config(st, self.spec)
            with spans.phase(spans.DISPATCH):
                out = csim.chip_train(
                    gp, gm, xb, tb, idx, cfg, lr_eff=float(lr) / M,
                    reconcile=reconcile)
            with spans.phase(spans.WRITEBACK):
                gp2, gm2, err, fcnt, bcnt = out
                self._scatter_back(gp2, gm2, *csim.kept_views(out, cfg))
                err = err.reshape(M, -1)
            self._apply_phase_counters(self.chip_train, fcnt, bcnt, Mc)
            with spans.phase(spans.ACCOUNT):
                bits = (2 * self.placement.dims[0] * self.input_bits
                        + self.placement.dims[-1] * hw.ADC_BITS_OUT)
                for c in self.chip_train:
                    c.samples += Mc
                    c.record_io(bits, Mc)
                self.train_link.record_samples(bits, M)
                self.train_link.record_reconcile(C * self._reconcile_bits())
                self.train_steps += 1
            return err

        acts, dps = self._forward(xb, self.chip_train)
        out = hard_sigmoid(dps[-1])
        delta = tb - out                                  # (C, Mc, O)

        for si in reversed(range(len(self.placement.stages))):
            st = self.placement.stages[si]
            r, ct = st.row_tiles, st.col_tiles
            if spec.error_quant:
                # shared full-scale across the farm: quantizing the global
                # tensor IS max-abs over every chip's shard (a farm_max
                # collective in the distributed view) — required for the
                # replicas to discretize on the same grid as the serial
                # chip (III.F step 1).
                flat = delta.reshape(M, -1)
                delta = (q.error_quantize(flat, spec.err_bits).dequantize()
                         .reshape(C, Mc, -1))
            local = delta * hard_sigmoid_deriv(dps[si])

            ds = jax.vmap(lambda l: _tile_cols(l, r, ct, st.cols))(local)
            dxs = self._run_bwd(ds, self._gp[si], self._gm[si])
            dx = (dxs.reshape(C, r, ct, Mc, st.rows).sum(axis=2)
                     .transpose(0, 2, 1, 3).reshape(C, Mc, r * st.rows))
            delta_prev = dx[..., 1:st.lmap.fan_in + 1]
            for c in self.chip_train:
                c.record_phase("bwd", st.n_cores, Mc)

            # update: LOCAL outer products (one farm-wide dispatch), then
            # the host reconciles and every replica pulses identically.
            xs = jax.vmap(lambda a: tile_inputs(a, r, ct, st.rows))(acts[si])
            dw_local = self._run_dw(xs, ds)               # (C, T, rows, cols)
            dw = 2.0 * (lr / M) * farm_reduce_sum(dw_local, mode=reconcile)
            if spec.update_quant:
                dw = q.pulse_discretize(dw, spec.max_update,
                                        spec.update_levels, None)
            self._gp[si] = jnp.clip(self._gp[si] + 0.5 * dw[None],
                                    0.0, spec.w_max)
            self._gm[si] = jnp.clip(self._gm[si] - 0.5 * dw[None],
                                    0.0, spec.w_max)
            for c in self.chip_train:
                c.record_phase("update", st.n_cores, Mc)

            delta = delta_prev

        bits = (2 * self.placement.dims[0] * self.input_bits
                + self.placement.dims[-1] * hw.ADC_BITS_OUT)
        for c in self.chip_train:
            c.samples += Mc
            c.record_io(bits, Mc)
        self.train_link.record_samples(bits, M)
        self.train_link.record_reconcile(C * self._reconcile_bits())
        self.train_steps += 1
        self.version += 1
        return (tb - out).reshape(M, -1)

    def _reconcile_bits(self) -> int:
        """Host-link bits one chip's update reconciliation moves per step:
        its local dw codes up + the reconciled pulses down, ERR_BITS_LINK
        bits per placed main-grid cell each way (measured from the actual
        dw stack sizes).  The wire format is always the paper's 8-bit
        codes — `hw_model.farm_cost` prices the same constant — so the
        metered traffic does not depend on the ``reconcile`` mode; "none"
        is a numerics idealization (exact f32 sum), not a wider link."""
        cells = sum(int(gp[0].size) for gp in self._gp)
        return 2 * cells * hw.ERR_BITS_LINK

    # ------------------------------------------------------------------
    # Serving
    # ------------------------------------------------------------------

    @spans.entry("farm.serve")
    def serve(self, x: jax.Array) -> tuple[jax.Array, dict]:
        """Serve a batch of requests (one per row) through the pipelined
        farm; returns (outputs in request order, serving stats)."""
        with spans.phase(spans.PREPARE):
            x = jnp.atleast_2d(x)
            if x.shape[0] == 0:
                return (jnp.zeros((0, self.placement.dims[-1])),
                        {"beats": 0, "retired": 0, "beat_us": self.beat_us,
                         "makespan_us": 0.0, "samples_per_s": 0.0,
                         "occupancy": 0.0})
            server = FarmServer(self)
            queue = RequestQueue(list(x))
        stats = server.run(queue)
        with spans.phase(spans.WRITEBACK):
            out = jnp.stack([r.reshape(-1) for r in queue.results()])
        return out, stats

    # ------------------------------------------------------------------
    # Introspection / reporting
    # ------------------------------------------------------------------

    @property
    def beat_us(self) -> float:
        """Steady-state pipeline beat of every chip (Table IV)."""
        return hw.pipeline_beat_us(self.placement.cols)

    def layers(self) -> list[dict[str, jax.Array]]:
        """Chip-0 replica's conductances as per-layer dicts (replicas are
        in lockstep under reconcile="none")."""
        return self.extract_chip(0).layers()

    def extract_chip(self, i: int) -> VirtualChip:
        """Materialize chip ``i`` as a standalone VirtualChip view."""
        stages = [dataclasses.replace(st, g_plus=self._gp[si][i],
                                      g_minus=self._gm[si][i])
                  for si, st in enumerate(self.placement.stages)]
        pl = Placement(stages=stages, dims=self.placement.dims,
                       rows=self.placement.rows, cols=self.placement.cols,
                       nmap=self.placement.nmap)
        return VirtualChip([], self.spec, name=f"{self.name}.chip{i}",
                           input_bits=self.input_bits, placement=pl)

    def replicas_in_sync(self) -> bool:
        """True when every chip holds bitwise-identical conductances."""
        for gp, gm in zip(self._gp, self._gm):
            for g in (gp, gm):
                if not bool(jnp.all(g == g[:1])):
                    return False
        return True

    def _chip_report(self, i: int) -> SimReport:
        inf, tr = self.chip_infer[i], self.chip_train[i]
        beat = self.beat_us
        return SimReport(
            name=f"{self.name}.chip{i}", dims=self.placement.dims,
            cores=self.placement.n_cores,
            infer_samples=inf.samples, train_samples=tr.samples,
            infer_time_us=inf.time_us() if inf.samples else 0.0,
            infer_energy_j=inf.core_energy_j() if inf.samples else 0.0,
            infer_io_j=inf.io_energy_j() if inf.samples else 0.0,
            train_time_us=tr.time_us() if tr.samples else 0.0,
            train_energy_j=(tr.core_energy_j(include_ctrl=True)
                            if tr.samples else 0.0),
            train_io_j=tr.io_energy_j() if tr.samples else 0.0,
            beat_us=beat, throughput_sps=1e6 / beat,
            routed_per_sample=(
                inf.noc.routed_outputs_per_sample(inf.samples)
                if inf.samples
                else tr.noc.routed_outputs_per_sample(tr.samples)),
            link_utilization=(inf.noc.link_utilization if inf.samples
                              else tr.noc.link_utilization),
        )

    def report(self) -> FarmReport:
        """Aggregate the per-chip counters + host-link tracker into a
        `FarmReport`, carrying the matching analytic `hw_model.farm_cost`
        for cross-validation (DESIGN.md §6.4)."""
        per_chip = tuple(self._chip_report(i) for i in range(self.n_chips))
        beat = self.beat_us
        serve_samples = self.serve_link.samples
        # capacity from FULL beats only (fill/drain and ragged final
        # beats are measurement artifacts, not reduced capacity); 0 when
        # no beat ever filled every slot — compare_hw then skips the
        # throughput comparison
        serve_sps = (self.serve_full_samples
                     / (self.serve_full_beats * beat) * 1e6
                     if self.serve_full_beats else 0.0)
        slot_m = (self.serve_full_samples / self.serve_full_requests
                  if self.serve_full_requests else 1.0)
        link = self.serve_link
        serve_bits = link.sample_bits_per_sample()
        # per-sample chip energy is uniform across wave-inferred and served
        # samples (each bills one full pipeline), so average over all of
        # them even when both paths ran.
        infer_samples = sum(r.infer_samples for r in per_chip)
        chip_serve_j = (sum(r.infer_total_j * r.infer_samples
                            for r in per_chip) / infer_samples
                        if infer_samples else 0.0)
        serve_j = chip_serve_j + link.energy_j(serve_bits)

        train_samples = sum(r.train_samples for r in per_chip)
        train_bits = self.train_link.sample_bits_per_sample()
        recon_bits = self.train_link.reconcile_bits_per_step()
        if self.train_steps:
            per_chip_batch = (train_samples // self.n_chips
                              // self.train_steps)
            chip_t = per_chip[0].train_time_us
            step_us = per_chip_batch * chip_t + self.train_link.time_us(
                recon_bits / self.n_chips)
            chip_train_j = sum(r.train_total_j * r.train_samples
                               for r in per_chip) / train_samples
            train_j = chip_train_j + self.train_link.energy_j(train_bits) \
                + self.train_link.energy_j(recon_bits) * self.train_steps \
                / train_samples
        else:
            per_chip_batch = 1
            step_us = train_j = 0.0
        analytic = hw.farm_cost(
            self.name, list(self.placement.dims), self.n_chips,
            batch_per_chip=max(per_chip_batch, 1),
            input_bits=self.input_bits,
            share_small_layers=self.share_small_layers,
            rows=self.placement.rows, cols=self.placement.cols)
        return FarmReport(
            name=self.name, n_chips=self.n_chips, dims=self.placement.dims,
            per_chip=per_chip, beat_us=beat,
            serve_samples=serve_samples, serve_beats=self.serve_beats,
            serve_samples_per_s=serve_sps, serve_j_per_sample=serve_j,
            train_samples=train_samples, train_steps=self.train_steps,
            train_step_us=step_us, train_j_per_sample=train_j,
            host_serve_bits=serve_bits, host_train_bits=train_bits,
            host_reconcile_bits=recon_bits,
            host_link_utilization=(link.time_us(serve_bits) / beat
                                   if serve_samples else 0.0),
            host_serve_bits_total=self.serve_link.sample_bits,
            host_train_bits_total=self.train_link.sample_bits,
            host_reconcile_bits_total=self.train_link.reconcile_bits,
            serve_slot_m=slot_m,
            analytic=analytic,
        )


def build_farm(app: str, n_chips: int, *, seed: int = 0,
               share_small_layers: bool = False, spec=None,
               mesh=None) -> ChipFarm:
    """A farm of ``n_chips`` replicas of one paper application."""
    from repro.configs.paper_apps import NETWORKS, PAPER_SPEC
    from repro.core import crossbar as xb
    spec = PAPER_SPEC if spec is None else spec
    dims = NETWORKS[app]
    key = jax.random.PRNGKey(seed)
    layers = [xb.init_conductances(jax.random.fold_in(key, i), f, o, spec)
              for i, (f, o) in enumerate(zip(dims, dims[1:]))]
    return ChipFarm(layers, spec, n_chips=n_chips, name=app,
                    share_small_layers=share_small_layers, mesh=mesh)


class FarmServer:
    """Pipelined serving front-end: one chip-axis stacked call per beat.

    Wavefront execution (Fig. 2 at farm scale): sample ``k`` occupies
    stage ``s`` of its chip at beat ``enter_k + s``; every beat the server
    assembles the (C, sumT, m, rows) input slab of ALL stages of ALL
    chips, runs ONE `crossbar_fwd_stacked` dispatch (plus one aggregation
    dispatch when fan-in-split stages exist), advances the wavefront, and
    refills each chip's stage-0 slot from the request queue.  Numerics are
    identical to the wave path — stages are sample-independent — so served
    outputs equal `mlp_forward` exactly; what the beat loop adds is the
    *time* structure the farm throughput claim is made from.
    """

    def __init__(self, farm: ChipFarm):
        self.farm = farm
        self._version = farm.version     # conductance snapshot guard
        pl = farm.placement
        self.stages = pl.stages
        self.S = len(self.stages)
        self.C = farm.n_chips
        self.rows = pl.rows
        # chip-major stacks: chip c's cores for all stages, concatenated
        self._off = []
        off = 0
        for st in self.stages:
            self._off.append(off)
            off += st.g_plus.shape[0]
        self.sumT = off
        self._stack_p = jnp.concatenate(farm._gp, axis=1)  # (C, sumT, R, cols)
        self._stack_m = jnp.concatenate(farm._gm, axis=1)
        # aggregation stacks (fan-in-split stages), padded to a common
        # input-line count
        self._agg_idx = [si for si, st in enumerate(self.stages)
                         if st.row_tiles > 1]
        if self._agg_idx:
            self._agg_rows = max(self.stages[si].agg_plus.shape[1]
                                 for si in self._agg_idx)
            self._agg_off = []
            parts_p, parts_m = [], []
            aoff = 0
            for si in self._agg_idx:
                st = self.stages[si]
                self._agg_off.append(aoff)
                aoff += st.agg_plus.shape[0]
                pad = self._agg_rows - st.agg_plus.shape[1]
                ap = jnp.pad(st.agg_plus, ((0, 0), (0, pad), (0, 0)))
                am = jnp.pad(st.agg_minus, ((0, 0), (0, pad), (0, 0)))
                parts_p.append(jnp.broadcast_to(ap, (self.C,) + ap.shape))
                parts_m.append(jnp.broadcast_to(am, (self.C,) + am.shape))
            self._agg_p = jnp.concatenate(parts_p, axis=1)
            self._agg_m = jnp.concatenate(parts_m, axis=1)
        # wavefront: pipe[c][s] = (rid, input activation) or None
        self.pipe: list[list] = [[None] * self.S for _ in range(self.C)]
        self._slot_m: int | None = None   # uniform request batch size

    # -- one pipeline beat ------------------------------------------------

    def step(self, queue: RequestQueue) -> int:
        """Advance the farm one beat; returns samples retired."""
        farm = self.farm
        if farm.version != self._version:
            raise RuntimeError(
                "farm conductances changed since this FarmServer was "
                "built (a train_step ran); construct a fresh server — "
                "the serving stacks are a snapshot")
        spec = farm.spec
        for c in range(self.C):
            if self.pipe[c][0] is None:
                req = queue.pop()
                if req is not None:
                    x = jnp.atleast_2d(jnp.asarray(req.x))
                    # the beat slab needs one static shape: all requests
                    # of a serving session must share their microbatch
                    if self._slot_m is None:
                        self._slot_m = x.shape[0]
                    elif x.shape[0] != self._slot_m:
                        raise ValueError(
                            f"request {req.rid} has microbatch "
                            f"{x.shape[0]}, session uses {self._slot_m}; "
                            f"serve uniform request shapes")
                    self.pipe[c][0] = (req.rid, x)
        m = next((h.shape[0] for lane in self.pipe
                  for slot in lane if slot is not None
                  for h in (slot[1],)), None)
        if m is None:
            return 0

        # assemble the farm-wide input slab (idle slots drive zeros; their
        # outputs are discarded and their stages not billed)
        slabs = []
        for c in range(self.C):
            parts = []
            for s, st in enumerate(self.stages):
                if self.pipe[c][s] is not None:
                    parts.append(tile_inputs(self.pipe[c][s][1],
                                             st.row_tiles, st.col_tiles,
                                             st.rows))
                else:
                    parts.append(jnp.zeros(
                        (st.g_plus.shape[0], m, st.rows)))
            slabs.append(jnp.concatenate(parts, axis=0))
        xs = jnp.stack(slabs)                       # (C, sumT, m, rows)
        ys = farm._run_fwd(xs, self._stack_p, self._stack_m)

        # aggregation dispatch for fan-in-split stages (same time slot);
        # input-line folding shared with the wave paths via
        # `placer.fold_subneuron_partials`
        agg_out = None
        if self._agg_idx:
            aparts = []
            for si in self._agg_idx:
                st = self.stages[si]
                o = self._off[si]
                u = fold_subneuron_partials(
                    ys[:, o:o + st.row_tiles * st.col_tiles], st)
                aparts.append(jnp.pad(
                    u, ((0, 0), (0, 0), (0, 0),
                        (0, self._agg_rows - u.shape[-1]))))
            agg_in = jnp.concatenate(aparts, axis=1)
            agg_out = farm._run_fwd(agg_in, self._agg_p, self._agg_m)

        # per-stage dot products -> outputs, advance the wavefront
        new_pipe: list[list] = [[None] * self.S for _ in range(self.C)]
        retired = 0
        retired_requests = 0
        for s, st in enumerate(self.stages):
            r, ct = st.row_tiles, st.col_tiles
            o = self._off[s]
            agg_slice = None
            if r > 1:
                ao = self._agg_off[self._agg_idx.index(s)]
                agg_slice = agg_out[:, ao:ao + ct]  # (C, ct, m, cols)
            dp = stage_dp_from_outputs(ys[:, o:o + r * ct], st, agg_slice)
            for c in range(self.C):
                if self.pipe[c][s] is None:
                    continue
                rid, _ = self.pipe[c][s]
                farm._count_stage([farm.chip_infer[c]], st, m)
                h = hard_sigmoid(dp[c])
                if s < self.S - 1:
                    if spec.transport_quant:
                        h = q.adc_quantize_ste(h, spec.adc_bits)
                    new_pipe[c][s + 1] = (rid, h)
                else:
                    queue.complete(rid, h)
                    retired += m
                    retired_requests += 1
                    bits = (farm.placement.dims[0] * farm.input_bits
                            + farm.placement.dims[-1] * hw.ADC_BITS_OUT)
                    farm.serve_link.record_samples(bits, m)
                    farm.chip_infer[c].samples += m
                    farm.chip_infer[c].record_io(bits, m)
        if retired_requests == self.C:      # every slot retired: capacity
            farm.serve_full_beats += 1
            farm.serve_full_samples += retired
            farm.serve_full_requests += retired_requests
        self.pipe = new_pipe
        farm.serve_beats += 1
        return retired

    def _run_compiled(self, queue: RequestQueue) -> dict:
        """The whole serving session as ONE jitted scan over beats
        (DESIGN.md §8): the wavefront schedule of `step` is static —
        request ``r`` enters chip ``r % C`` at beat ``r // C`` — so the
        beat loop compiles once and the queue is drained in a single
        device program.  Counters replay the same static schedule
        host-side (identical totals to the eager loop)."""
        farm = self.farm
        if farm.version != self._version:
            raise RuntimeError(
                "farm conductances changed since this FarmServer was "
                "built (a train_step ran); construct a fresh server — "
                "the serving stacks are a snapshot")
        farm.serve_sessions += 1
        C, S = self.C, self.S
        with spans.phase(spans.PREPARE):
            st, gp, gm = farm._get_stacks()
            gp_cat = jnp.moveaxis(gp, 0, 1).reshape(C, S * st.T_max,
                                                    st.rows, st.cols)
            gm_cat = jnp.moveaxis(gm, 0, 1).reshape(C, S * st.T_max,
                                                    st.rows, st.cols)
        Q, m, q_max, n_beats = csim.run_serve_session(
            queue, st, gp_cat, gm_cat, farm.spec, C)
        self._slot_m = m

        # counters: the eager loop's per-beat billing, aggregated over the
        # static schedule (lane c serves ceil((Q - c) / C) requests)
        with spans.phase(spans.ACCOUNT):
            bits = (farm.placement.dims[0] * farm.input_bits
                    + farm.placement.dims[-1] * hw.ADC_BITS_OUT)
            for c in range(C):
                n = (Q - c + C - 1) // C * m
                if not n:
                    continue
                cc = farm.chip_infer[c]
                for stg in self.stages:
                    cc.record_phase("fwd", stg.n_cores, n)
                    cc.noc.record(stg.index, stg.lmap.routed_outputs,
                                  stg.g_plus.shape[0], n)
                cc.samples += n
                cc.record_io(bits, n)
            farm.serve_link.record_samples(bits, Q * m)
            full = Q // C
            farm.serve_full_beats += full
            farm.serve_full_samples += full * C * m
            farm.serve_full_requests += full * C
            farm.serve_beats += n_beats
        beat_us = farm.beat_us
        return {
            "beats": n_beats,
            "retired": Q * m,
            "beat_us": beat_us,
            "makespan_us": n_beats * beat_us,
            "samples_per_s": Q * m / (q_max * beat_us) * 1e6,
            "occupancy": Q * self.S / max(self.S * self.C * n_beats, 1),
        }

    def run(self, queue: RequestQueue, *, max_beats: int | None = None
            ) -> dict:
        """Drain the queue; returns serving stats.

        With the compiled executor active, a fresh server draining a
        uniform-shape queue runs the whole session as one jitted beat
        scan; step-wise use (partially drained pipes, beat limits, ragged
        shapes) stays on the eager per-beat path."""
        with spans.phase(spans.PREPARE):
            compiled = (self.farm._compiled_active() and max_beats is None
                        and csim.serve_session_applicable(
                            queue, all(s is None for lane in self.pipe
                                       for s in lane), self._slot_m))
        if compiled:
            return self._run_compiled(queue)
        beats = retired = 0
        limit = max_beats if max_beats is not None else 10_000_000
        self.farm.serve_sessions += 1
        done_before = queue.completed
        while not queue.drained and beats < limit:
            retired += self.step(queue)
            beats += 1
        beat_us = self.farm.beat_us
        steady = max(beats - (self.S - 1), 1)
        requests = queue.completed - done_before
        return {
            "beats": beats,
            "retired": retired,
            "beat_us": beat_us,
            "makespan_us": beats * beat_us,
            "samples_per_s": retired / (steady * beat_us) * 1e6,
            # fraction of (chip, stage) slots occupied over the session
            "occupancy": requests * self.S / max(
                self.S * self.C * beats, 1),
        }
