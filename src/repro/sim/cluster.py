"""Chip farm: N virtual chips under one host (DESIGN.md §6).

The single-chip simulator (`repro.sim.chip`) executes one placed network;
the farm scales it out the way the ROADMAP's serving story requires:

  * ``ChipFarm`` — N data-parallel chip replicas.  Every stage's stacked
    conductances carry a leading *chip* axis ``(C, T, rows, cols)``, and
    a whole farm step runs as ONE compiled program
    (`compiled.chip_train`/`chip_infer`) whose chip axis is an array
    axis — never a Python loop over chips.

  * data-parallel training — each chip runs the paper's fwd/bwd phases on
    its batch shard, computes its LOCAL batch-summed outer product, and
    the host link reconciles (inside the same program):
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
    `runtime.serve_loop.RequestQueue` drained as one compiled scan over
    pipeline beats (`compiled.serve_scan`): request ``r`` enters chip
    ``r % C``'s stage-0 slot at beat ``r // C``, all stages of all chips
    evaluate in one stacked dispatch per beat, and each beat retires one
    sample per chip at steady state — Table IV's 0.77 us beat, times N.

  * accounting — per-chip `PhaseCounters` (identical conventions to the
    single chip, so the §5.3 contract holds per replica) plus a
    `HostLinkTracker` for sample ingress/egress and update-reconciliation
    traffic; `ChipFarm.report()` aggregates them into a `FarmReport`
    cross-validated against `hw_model.farm_cost`.

With a JAX device mesh (``mesh=`` with a ``"chips"`` axis), the farm is
sharded: every stage's stacks, and the compiled step's padded envelope
``(S, C, T_max, rows, cols)`` on its axis 1, are split over the devices by
chip, and the global batch by rows in replica order.  ``train_step``,
``infer`` and serving run the same compiled programs as one device, under
``shard_map``: each device runs its replicas' forward wave, backward scan
and local outer products (or its chips' serving lanes), and the
reconciliation (`farm_reduce_sum`) and the error full-scale (`farm_max`)
are collectives over ``"chips"`` inside that one donated program.  No
replica's stacks and no input row leave their device outside the
collectives.  Without a mesh the same code runs single-device (the chip
axis is just an array axis).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import hw_model as hw
from repro.core.crossbar import CORE_COLS, CORE_ROWS, CrossbarSpec
from repro.core.mapping import map_network
from repro.runtime.serve_loop import RequestQueue
from repro.sim import compiled as csim
from repro.sim import spans
from repro.sim.chip import VirtualChip, as_rows
from repro.sim.noc import NocTracker
from repro.sim.placer import (Placement, StageStacks, build_stage_stacks,
                              place_network)
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
    # Auto axes: the farm's host-side glue between its programs (envelope
    # builds, batch placement, serving's lane view) runs outside any mesh
    # context, which Explicit axes would reject
    return jax.make_mesh((span,), (csim.FARM_AXIS,),
                         axis_types=(jax.sharding.AxisType.Auto,))


def _envelope(stacks, T_max: int) -> jax.Array:
    """(S, C, T_max, rows, cols) from per-stage (C, T_s, rows, cols) stacks,
    the cores past ``T_s`` zero."""
    return jnp.stack([jnp.pad(g, ((0, 0), (0, T_max - g.shape[1]),
                                  (0, 0), (0, 0))) for g in stacks])


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
            # each replica's stacks, and its rows of a batch, live on the
            # device that runs its chip
            self._by_chip = NamedSharding(mesh, P(csim.FARM_AXIS))
            self._gp = [jax.device_put(g, self._by_chip) for g in self._gp]
            self._gm = [jax.device_put(g, self._by_chip) for g in self._gm]
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
        self._idx = None                          # index maps of _stacks
        self._held_scalar = (None, None)          # (value, device copy)
        self._stacks_version = -1
        self._serve_stacks = None                 # (layout, gp_cat, gm_cat)
        self._serve_stacks_version = -1

    # ------------------------------------------------------------------
    # Compiled whole-step executor (repro.sim.compiled, DESIGN.md §8)
    # ------------------------------------------------------------------

    def _compiled_active(self) -> bool:
        """Always True: the compiled programs are the farm's only way to
        run, on one device or on a mesh.  Kept because the benchmark's
        farm driver (``bench/drivers/farm.py``) asks before it times a
        meshed farm; drop it with that check."""
        return True

    def _get_stacks(self):
        """(layout, gp (S, C, T_max, rows, cols), gm, index maps) — padded
        chip-axis stacks, rebuilt when the conductance version moved
        outside the compiled step.  On a mesh the envelope is split by
        chip (axis 1) like the per-stage stacks, each device padding its
        own replicas, and every device holds the index maps, so a step
        moves neither."""
        if self._stacks is None or self._stacks_version != self.version:
            st = self._stacks = build_stage_stacks(self.placement)
            self._idx, out = st.index_pytree(), {}
            if self.mesh is not None:
                out = {"out_shardings": NamedSharding(
                    self.mesh, P(None, csim.FARM_AXIS))}
                self._idx = jax.device_put(
                    self._idx, NamedSharding(self.mesh, P()))
            pad = jax.jit(_envelope, static_argnums=1, **out)
            self._fgp = pad(tuple(self._gp), st.T_max)
            self._fgm = pad(tuple(self._gm), st.T_max)
            self._stacks_version = self.version
        return self._stacks, self._fgp, self._fgm, self._idx

    def _get_serve_stacks(self):
        """(layout, gp (C, S*T_max, rows, cols), gm) — the envelope as the
        serving scan takes it, each chip's stages concatenated on its
        lane; cut once per conductance version."""
        if self._serve_stacks_version != self.version:
            st, gp, gm, _ = self._get_stacks()
            cat = (self.n_chips, st.S * st.T_max, st.rows, st.cols)
            self._serve_stacks = (st, jnp.moveaxis(gp, 0, 1).reshape(cat),
                                  jnp.moveaxis(gm, 0, 1).reshape(cat))
            self._serve_stacks_version = self.version
        return self._serve_stacks

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

    def _rows(self, x: jax.Array, what: str) -> jax.Array:
        """The global batch as a meshed program takes it: rows in replica
        order, split over the devices (a batch that arrives so split is
        left where it is)."""
        if not isinstance(x, jax.Array) or x.ndim < 2:
            x = jnp.atleast_2d(x)
        if x.shape[0] % self.n_chips:
            raise ValueError(
                f"{what} batch {x.shape[0]} does not divide over "
                f"{self.n_chips} chips")
        if self.mesh is None or x.sharding == self._by_chip:
            return x
        return jax.device_put(x, self._by_chip)

    def _split(self, x: jax.Array, what: str) -> jax.Array:
        """(C, M / C, D): chip ``c`` takes the ``c``-th block of rows."""
        x = self._rows(x, what)
        M = x.shape[0]
        return x.reshape(self.n_chips, M // self.n_chips, x.shape[1])

    def _scalar(self, v: float):
        """``v`` as the compiled step takes it: on a mesh a float32 every
        device holds, placed once for each value, so a step with the
        same learning rate moves nothing to the devices."""
        if self.mesh is None:
            return v
        if self._held_scalar[0] != v:
            self._held_scalar = (v, jax.device_put(
                np.float32(v), NamedSharding(self.mesh, P())))
        return self._held_scalar[1]

    # ------------------------------------------------------------------
    # Inference (wave semantics; serving goes through FarmServer)
    # ------------------------------------------------------------------

    @spans.entry("farm.infer")
    def infer(self, x: jax.Array, *, count: bool = True) -> jax.Array:
        """Data-parallel recognition wave: the global batch splits over
        chips, each replica computes its shard; rows come back in input
        order and equal `VirtualChip.infer` on the unsharded batch."""
        with spans.phase(spans.PREPARE):
            xb = (self._split if self.mesh is None else self._rows)(
                x, "infer")
            Mc = (xb.shape[0] // self.n_chips if xb.ndim == 2
                  else xb.shape[1])
            st, gp, gm, idx = self._get_stacks()
            cfg = csim.chip_config(st, self.spec)
        with spans.phase(spans.DISPATCH):
            out, fcnt = csim.chip_infer(gp, gm, xb, idx, cfg, mesh=self.mesh)
        if count:
            self._apply_phase_counters(self.chip_infer, fcnt, None, Mc)
            with spans.phase(spans.ACCOUNT):
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
        # the whole farm step — chip-axis wave, reversed bwd scan,
        # farm_reduce_sum reconciliation INSIDE the trace, pulses broadcast
        # to every replica — is ONE donated XLA program, on a mesh split
        # over its devices by chip.
        with spans.phase(spans.PREPARE):
            batch = self._split if self.mesh is None else self._rows
            xb, tb = batch(x, "train"), batch(target, "target")
            C = self.n_chips
            M = xb.shape[0] if xb.ndim == 2 else C * xb.shape[1]
            Mc = M // C
            st, gp, gm, idx = self._get_stacks()
            cfg = csim.chip_config(st, self.spec)
            lr_eff = self._scalar(float(lr) / M)
        with spans.phase(spans.DISPATCH):
            out = csim.chip_train(gp, gm, xb, tb, idx, cfg, lr_eff=lr_eff,
                                  reconcile=reconcile, mesh=self.mesh)
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

    def _reconcile_bits(self) -> int:
        """Host-link bits one chip's update reconciliation moves per step:
        its local dw codes up + the reconciled pulses down, ERR_BITS_LINK
        bits per placed main-grid cell each way (measured from the actual
        dw stack sizes).  The wire format is always the paper's 8-bit
        codes — `hw_model.farm_cost` prices the same constant — so the
        metered traffic does not depend on the ``reconcile`` mode; "none"
        is a numerics idealization (exact f32 sum), not a wider link."""
        cells = sum(gp.size // gp.shape[0] for gp in self._gp)
        return 2 * cells * hw.ERR_BITS_LINK

    # ------------------------------------------------------------------
    # Serving
    # ------------------------------------------------------------------

    @spans.entry("farm.serve")
    def serve(self, x: jax.Array) -> tuple[jax.Array, dict]:
        """Serve a batch of requests (one per row) through the pipelined
        farm; returns (outputs in request order, serving stats)."""
        with spans.phase(spans.PREPARE):
            x = as_rows(x)
            if x.shape[0] == 0:
                return (jnp.zeros((0, self.placement.dims[-1])),
                        {"beats": 0, "retired": 0, "beat_us": self.beat_us,
                         "makespan_us": 0.0, "samples_per_s": 0.0,
                         "occupancy": 0.0})
            server = FarmServer(self)
            queue = RequestQueue(list(x))
        stats, out = server.session(queue)
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
    """Pipelined serving front-end: the whole session is one compiled
    scan over beats (`compiled.serve_scan`, DESIGN.md §6.3).

    Wavefront schedule (Fig. 2 at farm scale): request ``r`` enters chip
    ``r % C``'s stage-0 slot at beat ``r // C`` and occupies stage ``s``
    at beat ``r // C + s``; every beat evaluates all stages of all chips
    in one stacked dispatch and each beat retires one request per chip at
    steady state.  Numerics are the wave path's — stages are
    sample-independent — so served outputs equal `mlp_forward`; what the
    schedule adds is the *time* structure the farm throughput claim is
    made from, billed host-side in closed form.  On a mesh each device
    runs its own chips' lanes.  A server serves the conductance version
    it was built at and one request microbatch over its lifetime.
    """

    def __init__(self, farm: ChipFarm):
        self.farm = farm
        self._version = farm.version     # conductance snapshot guard
        self.stages = farm.placement.stages
        self.S = len(self.stages)
        self.C = farm.n_chips
        self._slot_m: int | None = None   # uniform request batch size

    def run(self, queue: RequestQueue) -> dict:
        """Drain the queue in one session; returns serving stats."""
        return self.session(queue)[0]

    def session(self, queue: RequestQueue) -> tuple[dict, jax.Array]:
        """Drain the queue in one session; returns (serving stats, the
        session's answers as one (requests * m, out_dim) array).  The
        counters replay the static schedule host-side: lane ``c`` serves
        ``ceil((Q - c) / C)`` requests."""
        farm = self.farm
        C, S = self.C, self.S
        with spans.phase(spans.PREPARE):
            csim.check_fresh("FarmServer", self._version, farm.version)
            st, gp_cat, gm_cat = farm._get_serve_stacks()
        Q, m, q_max, n_beats, out = csim.run_serve_session(
            queue, st, gp_cat, gm_cat, farm.spec, C, self._slot_m,
            mesh=farm.mesh)
        self._slot_m = m
        with spans.phase(spans.ACCOUNT):
            farm.serve_sessions += 1
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
            # fraction of (chip, stage) slots occupied over the session
            "occupancy": Q * S / max(S * C * n_beats, 1),
        }, out
