"""Pipeline-parallel chip fabric: one network split ACROSS chips.

The farm (`repro.sim.cluster`) replicates whole chips data-parallel, so a
network whose placed core count exceeds one chip's budget cannot run at
all.  This module is the other scaling axis (DESIGN.md §7): the mapper's
stage list is partitioned into contiguous per-chip groups
(`core.mapping.split_network`), each group executes on its own virtual
chip exactly as before (one compiled program per phase), and the
two values that cross a chip boundary obey the NoC's
quantize-at-the-boundary rule, lifted to a modeled inter-chip link:

  * forward: the boundary activation crosses as 3-bit output-ADC codes —
    the serial chip quantizes between stages anyway, so the split is
    *bitwise invisible* to the numerics;
  * backward: the error returns as 8-bit sign-magnitude codes — the
    serial training loop quantizes the error at the top of every stage
    iteration (III.F step 1), so again the boundary adds no new operation,
    only a place to *meter* it.

Consequently `ChipPipeline.train_step` equals the serial
`VirtualChip.train_step` on the unsplit network bitwise (pinned by
``tests/test_pipeline_fabric.py``), and what the fabric adds is structure
and accounting:

  * `ChipPipeline` — K chip slices executing the wave fwd / bwd / update
    phases in pipeline order, with per-slice `PhaseCounters` and an
    `InterChipLinkTracker` metering every boundary crossing;
  * a 1F1B *time* model — the executed numerics are the full-batch wave
    (the paper's training unit applies pulse updates once per batch, so
    microbatch staggering cannot change the update under the farm's
    shared-error-full-scale discipline); the `n_micro` 1F1B schedule is
    priced by `hw_model.schedule_1f1b` from the measured slice times and
    cross-validated against `hw_model.pipeline_cost`;
  * `PipelineServer` — drains a `runtime.serve_loop.RequestQueue` through
    the chip pipeline, billed at one beat per stage hop: a boundary hop
    rides inside the static routing slot (flagged by ``link_utilization``
    when it would not fit), and one sample retires per beat at steady
    state — the Table IV beat survives the split.  On the device the
    session is one recognition wave of all its requests
    (`compiled.serve_scan`);
  * `ChipPipeline.pretrain_step` — greedy layer-wise autoencoder
    pretraining (sections III.C-E) of a pipeline built with temporary
    decoders: each hidden layer's path (frozen encoders, the encoder, its
    decoder) is split over the chips like a network, the prefix stays
    frozen, and the decoder's error crosses back as 8-bit codes into the
    encoder's chip;
  * `PipelineFarm` — the composition point with the data-parallel farm: N
    lockstep replicas of a K-chip pipeline ("farm of pipelines").  The
    replica axis delegates to `ChipFarm` (reconciled pulse updates, host
    link), the pipeline axis adds the per-replica boundary metering.

All measured quantities cross-validate against ``hw_model.pipeline_cost``
to <= 1% — the §5.3 contract extended to the inter-chip link, enforced by
``python -m repro.launch.pipeline`` and ``benchmarks/bench_pipeline.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import hw_model as hw
from repro.core.crossbar import CORE_COLS, CORE_ROWS, CrossbarSpec
from repro.core.mapping import map_network, split_network
from repro.runtime.serve_loop import RequestQueue
from repro.sim import compiled as csim
from repro.sim import spans
from repro.sim.chip import Pretraining, VirtualChip, as_rows
from repro.sim.placer import (Placement, StageStacks, build_stage_stacks,
                              place_network, sub_placement)
from repro.sim.report import (InterChipLinkTracker, PipelineReport,
                              PretrainReport)


class ChipPipeline(Pretraining):
    """A network pipeline-split over K virtual chips (DESIGN.md §7).

    Built over ``layers`` or, as `VirtualChip` is, over a ``placement``.
    Given ``decoders`` (one temporary decoder per hidden layer), the
    pipeline holds them as placed cores for the whole greedy schedule of
    `pretrain_step`, as `VirtualChip` does."""

    def __init__(self, layers: list[dict[str, jax.Array]],
                 spec: CrossbarSpec | None = None, *,
                 max_cores_per_chip: int | None = None,
                 n_chips: int | None = None,
                 rows: int = CORE_ROWS, cols: int = CORE_COLS,
                 name: str = "pipeline", share_small_layers: bool = False,
                 input_bits: int = 8, placement: Placement | None = None,
                 decoders: list[dict] | None = None):
        if spec is None:
            from repro.configs.paper_apps import PAPER_SPEC
            spec = PAPER_SPEC
        if spec.split_activation:
            raise NotImplementedError(
                "the pipeline fabric inherits the virtual chip's "
                "exact-aggregation restriction (split_activation=False)")
        self.spec = spec
        self.name = name
        self.input_bits = input_bits
        self.share_small_layers = share_small_layers
        if max_cores_per_chip is None and n_chips is None:
            # default chip budget: the paper's 144-core system (Sec. VI)
            max_cores_per_chip = hw.SYSTEM_CORES
        self._split_kw = dict(max_cores_per_chip=max_cores_per_chip,
                              n_chips=n_chips)
        if placement is None:
            dims = [int(layers[0]["g_plus"].shape[0])] + \
                   [int(p["g_plus"].shape[1]) for p in layers]
            nmap = map_network(dims, rows, cols,
                               share_small_layers=share_small_layers)
            placement = place_network(layers, nmap, rows, cols)
        self.placement: Placement = placement
        dims = placement.dims
        self.groups = split_network(placement.nmap, **self._split_kw)
        self.n_chips = len(self.groups)
        self.chips = [
            VirtualChip([], spec, name=f"{name}.pp{k}",
                        input_bits=input_bits,
                        placement=sub_placement(self.placement, g))
            for k, g in enumerate(self.groups)]
        # boundary k sits between chips k and k+1; its width is the
        # activation dimension leaving chip k's last stage
        self.boundary_dims = tuple(dims[g[-1] + 1] for g in self.groups[:-1])
        self.link = InterChipLinkTracker()
        self.version = 0              # bumped on every conductance write
        self.serve_beats = 0
        self.serve_samples = 0
        self.serve_full_beats = 0     # beats that retired a request
        self.serve_slot_m = 1.0       # request microbatch (measured)
        self.train_steps = 0
        self.train_samples = 0
        self.batch_per_step = 1
        self.n_micro = 1
        self._serve_stacks = None
        self._serve_stacks_version = -1
        if decoders is not None and share_small_layers:
            raise NotImplementedError(
                "pretraining places every layer on cores of its own")
        self._hold_decoders(decoders)

    def _get_serve_stacks(self) -> StageStacks:
        """Padded full-placement stacks for the compiled serving wave —
        rebuilt when the fabric's conductances moved (``self.version``
        tracks every train step; the chip slices alias the parent's
        `Stage` objects, so a rebuild always sees their latest writes)."""
        if (self._serve_stacks is None
                or self._serve_stacks_version != self.version):
            self._serve_stacks = build_stage_stacks(self.placement)
            self._serve_stacks_version = self.version
        return self._serve_stacks

    # ------------------------------------------------------------------
    # Wave execution (numerics identical to the serial chip)
    # ------------------------------------------------------------------

    @spans.entry("pipeline.infer")
    def infer(self, x: jax.Array, *, count: bool = True) -> jax.Array:
        """One recognition wave through the chip pipeline.  Equals the
        serial `VirtualChip.infer` on the unsplit network bitwise: the
        boundary ADC is the same 3-bit quantization the serial chip
        applies between stages."""
        with spans.phase(spans.PREPARE):
            h = jnp.atleast_2d(x)
        M = h.shape[0]
        last = self.n_chips - 1
        for k, chip in enumerate(self.chips):
            _, _, h = chip.forward_wave(h, count=count,
                                        quantize_tail=k < last)
        if count:
            with spans.phase(spans.ACCOUNT):
                for k, chip in enumerate(self.chips):
                    chip.infer_counters.samples += M
                    if k < last:
                        self.link.record_fwd(
                            k, self.boundary_dims[k] * hw.ADC_BITS_OUT, M)
                self.chips[0].infer_counters.record_io(
                    self.placement.dims[0] * self.input_bits, M)
                self.chips[-1].infer_counters.record_io(
                    self.placement.dims[-1] * hw.ADC_BITS_OUT, M)
        return h

    @spans.entry("pipeline.train_step")
    def train_step(self, x: jax.Array, target: jax.Array, lr: float, *,
                   n_micro: int = 1) -> jax.Array:
        """One stochastic-BP step across the chip pipeline, bitwise equal
        to the serial `VirtualChip.train_step` on the unsplit network.

        The executed numerics are the full-batch wave: fwd chip 0 -> K-1
        (activations crossing each boundary as ADC codes), then bwd +
        update chip K-1 -> 0 (errors crossing back as 8-bit codes, pulse
        updates written in place per stage).  ``n_micro`` selects the
        1F1B *time* model for the step (span / bubble in the report);
        it cannot change the numerics because the pulse update applies
        once per batch with a shared error full-scale (the same argument
        that makes the farm equal the serial chip, DESIGN.md §6.2)."""
        with spans.phase(spans.PREPARE):
            x, target = as_rows(x), as_rows(target)
        if x.shape[0] % n_micro:
            raise ValueError(f"batch {x.shape[0]} not divisible by n_micro "
                             f"{n_micro}")
        return self._train(x, target, lr, n_micro=n_micro)

    def _owner(self, first: int) -> tuple[int, int]:
        """The chip holding stage ``first``, and the stage's index in that
        chip's slice."""
        k = next(k for k, g in enumerate(self.groups) if first in g)
        return k, self.groups[k].index(first)

    @staticmethod
    def _pretrain_target(wave, first: int) -> jax.Array:
        """The autoencoder target: the representation entering the slice's
        stage ``first`` as its chip's forward program took it in."""
        acts, _ = wave
        return acts[first]

    def _train(self, x: jax.Array, target: jax.Array | None, lr: float, *,
               first: int = 0, n_micro: int = 1) -> jax.Array:
        """The step of `train_step` from stage ``first`` on: the forward
        wave through every chip, then bwd + update from the last chip back
        to the chip holding stage ``first``, whose program starts the
        updates at that stage, so the stages below stay frozen.
        ``target=None`` is the autoencoder target of layer-wise
        pretraining, the representation entering stage ``first``
        (`_pretrain_target`).  Returns the error."""
        M = x.shape[0]
        last = self.n_chips - 1
        owner, local = self._owner(first)
        ae = target is None

        h = x
        waves = []
        for k, chip in enumerate(self.chips):
            acts, dps, h = chip.forward_wave(h, train=True,
                                             quantize_tail=k < last)
            waves.append((acts, dps))
        with spans.phase(spans.WRITEBACK):
            # the step's result, and the error the last chip takes back
            if ae:
                target = self._pretrain_target(waves[owner], local)
            err = target - h
        delta = err
        for k in reversed(range(owner, self.n_chips)):
            acts, dps = waves[k]
            delta = self.chips[k].backward_update(
                acts, dps, delta, lr, global_batch=M,
                first=local if k == owner else 0)

        with spans.phase(spans.ACCOUNT):
            for k, chip in enumerate(self.chips):
                chip.train_counters.samples += M
                if k < last:
                    self.link.record_fwd(
                        k, self.boundary_dims[k] * hw.ADC_BITS_OUT, M)
            for k in reversed(range(owner + 1, self.n_chips)):
                self.link.record_bwd(
                    k - 1, self.boundary_dims[k - 1] * hw.ERR_BITS_LINK, M)
            # IO: the input (and a supervised target) in, the output out
            self.chips[0].train_counters.record_io(
                (1 if ae else 2) * self.placement.dims[0] * self.input_bits,
                M)
            self.chips[-1].train_counters.record_io(
                self.placement.dims[-1] * hw.ADC_BITS_OUT, M)
            self.train_steps += 1
            self.train_samples += M
            self.batch_per_step = M
            self.n_micro = n_micro
            self.version += 1
            self._stale()
        return err

    # ------------------------------------------------------------------
    # Layer-wise pretraining (sections III.C-E)
    # ------------------------------------------------------------------

    def _over(self, placement: Placement, name: str) -> "ChipPipeline":
        return ChipPipeline([], self.spec, name=name,
                            input_bits=self.input_bits, placement=placement,
                            **self._split_kw)

    def _moved(self) -> None:
        """The placement's conductances moved outside this pipeline's own
        steps: every chip's next call rebuilds its envelope."""
        self.version += 1
        for chip in self.chips:
            chip._moved()

    @spans.entry("pipeline.pretrain_step")
    def pretrain_step(self, layer: int, x: jax.Array,
                      lr: float) -> jax.Array:
        """One greedy pretraining step of hidden layer ``layer`` on the
        batch ``x`` of network inputs: `_train` from encoder ``layer`` on
        the pipeline over the layer's path (frozen encoders, the encoder,
        its decoder), split over chips as a network is.  Activations cross
        each boundary as 3-bit codes; the target is the representation
        that entered encoder ``layer``; errors cross back as 8-bit codes
        as far as the chip holding encoder ``layer``, and no frozen stage
        is written.  Equals `VirtualChip.pretrain_step` within float
        re-association.  Returns the reconstruction error."""
        path = self._pretrain_path(layer)
        with spans.phase(spans.PREPARE):
            x = as_rows(x)
        err = path._train(x, None, lr, first=layer)
        with spans.phase(spans.ACCOUNT):
            self._pretrained(layer)
        return err

    # ------------------------------------------------------------------
    # Serving
    # ------------------------------------------------------------------

    @spans.entry("pipeline.serve")
    def serve(self, x: jax.Array) -> tuple[jax.Array, dict]:
        """Serve a batch of requests (one per row) through the pipelined
        fabric; returns (outputs in request order, serving stats)."""
        with spans.phase(spans.PREPARE):
            x = as_rows(x)
            if x.shape[0] == 0:
                return (jnp.zeros((0, self.placement.dims[-1])),
                        {"beats": 0, "retired": 0, "beat_us": self.beat_us,
                         "makespan_us": 0.0, "samples_per_s": 0.0,
                         "latency_us": self.serve_latency_us})
            server = PipelineServer(self)
            queue = RequestQueue(list(x))
        stats, out = server.session(queue)
        return out, stats

    # ------------------------------------------------------------------
    # Introspection / reporting
    # ------------------------------------------------------------------

    @property
    def beat_us(self) -> float:
        """Steady-state pipeline beat — unchanged by the chip split (a
        boundary hop rides inside the static routing slot)."""
        return hw.pipeline_beat_us(self.placement.cols)

    @property
    def serve_latency_us(self) -> float:
        """Serving latency: one beat per stage hop through the fabric."""
        return len(self.placement.stages) * self.beat_us

    def layers(self) -> list[dict[str, jax.Array]]:
        """Current conductances as per-layer dicts — the chip slices alias
        the full placement's stages, so this sees every chip's updates."""
        return self.placement.extract_params()

    def report(self) -> PipelineReport:
        """Aggregate the per-slice counters + link tracker into a
        `PipelineReport`, carrying the matching analytic
        `hw_model.pipeline_cost` for cross-validation."""
        per_chip = tuple(c.report() for c in self.chips)
        beat = self.beat_us
        link = self.link
        fwd_bps = link.fwd_bits_per_sample()
        bwd_bps = link.bwd_bits_per_sample()

        # serving: capacity is measured over beats that retired a request
        # only — fill/drain beats are a measurement artifact of short
        # sessions, not reduced fabric capacity (same rule as the farm)
        serve_sps = (self.serve_samples / (self.serve_full_beats * beat)
                     * 1e6 if self.serve_full_beats else 0.0)
        infer_samples = max((r.infer_samples for r in per_chip), default=0)
        serve_j = (sum(r.infer_total_j for r in per_chip)
                   + link.energy_j(fwd_bps)) if infer_samples else 0.0
        link_util = max(
            (link.time_us(link.fwd_bits[b] / max(link.fwd_samples, 1))
             / beat for b in link.fwd_bits), default=0.0)

        # training: the executed wave, per-slice counters partitioning the
        # serial chip's counters exactly
        if self.train_steps:
            counters = [c.train_counters for c in self.chips]
            t_slices = [c.time_us() for c in counters]
            B = self.batch_per_step
            step_bits = B * (fwd_bps + bwd_bps)
            train_step_us = B * sum(t_slices) + link.time_us(step_bits)
            # control logic burns on every placed core for the whole step
            # (the serial convention — the slices hold one shared step)
            total_fwd_cores = sum(c.core_steps["fwd"] / max(c.samples, 1)
                                  for c in counters)
            train_core_j = sum(c.core_energy_j(include_ctrl=False)
                               for c in counters) \
                + hw.core_step_energy_j(sum(t_slices), hw.CTRL_MW,
                                        total_fwd_cores)
            train_j = train_core_j \
                + sum(c.io_energy_j() for c in counters) \
                + link.energy_j(fwd_bps + bwd_bps)
            # 1F1B schedule from the measured slice times
            u = B // self.n_micro
            fwd_us = [u * (c.slots["fwd"] / max(c.samples, 1) * hw.FWD_US
                           + c.route_us()) for c in counters]
            bwd_us = [u * (c.slots["bwd"] / max(c.samples, 1) * hw.BWD_US
                           + c.slots["update"] / max(c.samples, 1)
                           * hw.UPD_US) for c in counters]
            n_samples = max(link.fwd_samples, 1)
            link_f = [u * link.time_us(link.fwd_bits.get(b, 0) / n_samples)
                      for b in range(self.n_chips - 1)]
            link_b = [u * link.time_us(link.bwd_bits.get(b, 0)
                                       / max(link.bwd_samples, 1))
                      for b in range(self.n_chips - 1)]
            span = hw.schedule_1f1b(fwd_us, bwd_us, link_f, link_b,
                                    self.n_micro)
            # per-chip busy time over the step = n_micro microbatch slices
            busy = self.n_micro * sum(f + b for f, b in zip(fwd_us, bwd_us))
            bubble = 1.0 - busy / (self.n_chips * span) if span else 0.0
        else:
            train_step_us = train_j = span = 0.0
            bubble = 0.0

        analytic = hw.pipeline_cost(
            self.name, list(self.placement.dims),
            batch=self.batch_per_step, n_micro=self.n_micro,
            input_bits=self.input_bits,
            share_small_layers=self.share_small_layers,
            rows=self.placement.rows, cols=self.placement.cols,
            **self._split_kw)
        return PipelineReport(
            name=self.name, n_chips=self.n_chips,
            dims=self.placement.dims, stage_groups=self.groups,
            cores_per_chip=tuple(c.placement.n_cores for c in self.chips),
            per_chip=per_chip, beat_us=beat,
            serve_samples=self.serve_samples, serve_beats=self.serve_beats,
            serve_samples_per_s=serve_sps, serve_j_per_sample=serve_j,
            serve_latency_us=self.serve_latency_us,
            link_utilization=link_util,
            train_samples=self.train_samples, train_steps=self.train_steps,
            train_step_us=train_step_us, train_j_per_sample=train_j,
            link_bits_fwd=fwd_bps, link_bits_bwd=bwd_bps,
            link_bits_total=link.fwd_bits_total + link.bwd_bits_total,
            span_us=span, bubble_fraction=bubble,
            n_micro=self.n_micro, batch_per_step=self.batch_per_step,
            serve_slot_m=self.serve_slot_m, analytic=analytic,
            pretrain=tuple(self._pretrain_report(k) for k in
                           sorted(self._paths)
                           if self._paths[k].train_samples))

    def _pretrain_report(self, layer: int) -> PretrainReport:
        """Hidden layer ``layer``'s pretraining counters, summed over its
        path's chips, beside the analytic cost of its step split as the
        path is."""
        path, pl = self._paths[layer], self.placement
        return PretrainReport.measure(
            layer, [c.train_counters for c in path.chips],
            hw.pretrain_step_cost(self.name, list(pl.dims), layer,
                                  rows=pl.rows, cols=pl.cols,
                                  input_bits=self.input_bits,
                                  **self._split_kw),
            link=path.link)


def build_pipeline(app: str, *, max_cores_per_chip: int | None = None,
                   n_chips: int | None = None, seed: int = 0,
                   share_small_layers: bool = False,
                   spec=None) -> ChipPipeline:
    """A pipeline fabric executing one paper application."""
    from repro.configs.paper_apps import NETWORKS, PAPER_SPEC
    from repro.core import crossbar as xb
    spec = PAPER_SPEC if spec is None else spec
    dims = NETWORKS[app]
    key = jax.random.PRNGKey(seed)
    layers = [xb.init_conductances(jax.random.fold_in(key, i), f, o, spec)
              for i, (f, o) in enumerate(zip(dims, dims[1:]))]
    return ChipPipeline(layers, spec, max_cores_per_chip=max_cores_per_chip,
                        n_chips=n_chips, name=app,
                        share_small_layers=share_small_layers)


class PipelineServer:
    """Pipelined serving front-end over the chip fabric.

    The session is billed as a beat schedule: a request occupies one
    global stage per beat, and the hop rides inside the beat's static
    routing slot, so the Table IV beat — and therefore the
    one-sample-per-beat steady state — survives the split.  On the device
    it is one recognition wave (`compiled.serve_scan`): the fabric is the
    single-lane (C == 1) case of the farm's session, all its requests
    one batch through the FULL placement's stage stacks.  The boundary
    quantize rule is the wave's ordinary inter-stage ADC; a sample
    crossing a chip boundary is metered on the inter-chip link, replayed
    host-side from the static owner map.  Stages are sample-independent,
    so the answers are the beat schedule's, and equal `mlp_forward`.  A
    server serves the conductance version it was built at and one
    request microbatch over its lifetime."""

    def __init__(self, pipe: ChipPipeline):
        self.pipe = pipe
        self._version = pipe.version     # conductance snapshot guard
        self.stages = pipe.placement.stages
        self.S = len(self.stages)
        # global stage index -> owning chip
        self.owner = [k for k, g in enumerate(pipe.groups) for _ in g]
        self._slot_m: int | None = None

    def run(self, queue: RequestQueue) -> dict:
        """Drain the queue in one session; returns serving stats."""
        return self.session(queue)[0]

    def session(self, queue: RequestQueue) -> tuple[dict, jax.Array]:
        """Drain the queue in one session; returns (serving stats, the
        session's answers as one (requests * m, out_dim) array).  The
        counters replay the static schedule host-side: every request
        visits every stage once."""
        pipe = self.pipe
        S = self.S
        with spans.phase(spans.PREPARE):
            csim.check_fresh("PipelineServer", self._version, pipe.version)
            st = pipe._get_serve_stacks()
        Q, m, _, n_beats, out = csim.run_serve_session(
            queue, st, st.g_plus, st.g_minus, pipe.spec, 1, self._slot_m)
        self._slot_m = m
        with spans.phase(spans.ACCOUNT):
            n = Q * m
            for s, stg in enumerate(self.stages):
                cc = pipe.chips[self.owner[s]].infer_counters
                cc.record_phase("fwd", stg.n_cores, n)
                cc.noc.record(stg.index, stg.lmap.routed_outputs,
                              stg.g_plus.shape[0], n)
            for s in range(S - 1):
                k = self.owner[s]
                if self.owner[s + 1] != k:
                    pipe.link.record_fwd(
                        k, pipe.boundary_dims[k] * hw.ADC_BITS_OUT, n)
            pipe.chips[0].infer_counters.record_io(
                pipe.placement.dims[0] * pipe.input_bits, n)
            pipe.chips[self.owner[S - 1]].infer_counters.record_io(
                pipe.placement.dims[-1] * hw.ADC_BITS_OUT, n)
            for c in pipe.chips:
                c.infer_counters.samples += n
            pipe.serve_full_beats += Q
            pipe.serve_beats += n_beats
            pipe.serve_samples += n
            pipe.serve_slot_m = m
        beat_us = pipe.beat_us
        return {
            "beats": n_beats,
            "retired": n,
            "beat_us": beat_us,
            "makespan_us": n_beats * beat_us,
            "latency_us": pipe.serve_latency_us,
            "samples_per_s": n / (Q * beat_us) * 1e6,
            # fraction of stage slots occupied over the session
            "occupancy": Q * S / max(S * n_beats, 1),
        }, out


class PipelineFarm:
    """Farm of pipelines: N data-parallel replicas of a K-chip pipeline.

    The composition point of the repo's two scaling axes (DESIGN.md §7.4):
    the replica axis is a `ChipFarm` (chip-axis stacked dispatch,
    reconciled pulse updates over the host link — every DP guarantee of
    §6 carries over verbatim, including bitwise lockstep and equality
    with the serial chip), and the pipeline axis is the stage split of
    `ChipPipeline`, metered per replica on the inter-chip link.  Total
    chips = ``n_pipelines x n_chips_per_pipeline``."""

    def __init__(self, layers: list[dict[str, jax.Array]],
                 spec: CrossbarSpec | None = None, *,
                 n_pipelines: int = 2,
                 max_cores_per_chip: int | None = None,
                 n_chips: int | None = None,
                 rows: int = CORE_ROWS, cols: int = CORE_COLS,
                 name: str = "pipeline_farm",
                 share_small_layers: bool = False,
                 input_bits: int = 8, mesh=None):
        from repro.sim.cluster import ChipFarm
        self.farm = ChipFarm(layers, spec, n_chips=n_pipelines, rows=rows,
                             cols=cols, name=name,
                             share_small_layers=share_small_layers,
                             input_bits=input_bits, mesh=mesh)
        if max_cores_per_chip is None and n_chips is None:
            max_cores_per_chip = hw.SYSTEM_CORES
        self.groups = split_network(self.farm.placement.nmap,
                                    max_cores_per_chip=max_cores_per_chip,
                                    n_chips=n_chips)
        dims = self.farm.placement.dims
        self.boundary_dims = tuple(dims[g[-1] + 1] for g in self.groups[:-1])
        self.n_pipelines = n_pipelines
        self.n_chips_per_pipeline = len(self.groups)
        self.link = InterChipLinkTracker()

    @property
    def total_chips(self) -> int:
        """Physical chips in the composed fabric (replicas x stages)."""
        return self.n_pipelines * self.n_chips_per_pipeline

    def train_step(self, x: jax.Array, target: jax.Array, lr: float, *,
                   reconcile: str = "none") -> jax.Array:
        """One data-parallel step over the pipeline replicas (numerics ==
        `ChipFarm.train_step` == the serial chip); every replica's wave
        crosses its pipeline boundaries with its batch shard, metered on
        the inter-chip link."""
        err = self.farm.train_step(x, target, lr, reconcile=reconcile)
        M = jnp.atleast_2d(x).shape[0]       # global batch over replicas
        for b, d in enumerate(self.boundary_dims):
            self.link.record_fwd(b, d * hw.ADC_BITS_OUT, M)
            self.link.record_bwd(b, d * hw.ERR_BITS_LINK, M)
        return err

    def serve(self, x: jax.Array) -> tuple[jax.Array, dict]:
        """Serve through the farm front-end; each retired sample crossed
        every pipeline boundary of its replica once."""
        out, stats = self.farm.serve(x)
        M = stats["retired"]
        for b, d in enumerate(self.boundary_dims):
            self.link.record_fwd(b, d * hw.ADC_BITS_OUT, M)
        return out, stats

    def replicas_in_sync(self) -> bool:
        """True when every pipeline replica holds identical conductances."""
        return self.farm.replicas_in_sync()

    def layers(self) -> list[dict[str, jax.Array]]:
        """Replica-0 conductances as per-layer dicts."""
        return self.farm.layers()

    def report(self):
        """(FarmReport, per-sample pipeline-link bits fwd/bwd) — the DP
        axis cross-validates via the farm contract, the pipeline axis via
        `hw_model.pipeline_cost` link bits."""
        return (self.farm.report(),
                {"link_bits_fwd": self.link.fwd_bits_per_sample(),
                 "link_bits_bwd": self.link.bwd_bits_per_sample()})
