"""VirtualChip: execute networks on the simulated multicore grid.

The executable counterpart of `core/hw_model.py` (DESIGN.md "Virtual
chip").  A chip is a `Placement` (stacked per-core conductances, one stage
per layer) plus counters; it runs:

  * ``infer``        — one wave through the stages, serialized-latency
                       semantics (the analytic model's recognition pass);
  * ``infer_stream`` — pipelined streaming (Fig. 2): consecutive samples
                       occupy consecutive stages, steady-state throughput
                       is one sample per beat = crossbar eval + one static
                       routing slot (Table IV's 0.77 us);
  * ``train_step``   — the paper's three phases per layer (Table II):
                       fwd (record inputs + DPs), bwd (8-bit errors through
                       the same conductances), update (pulse-discretized
                       outer product written into the stacks in place);
  * ``pretrain_step`` — greedy layer-wise autoencoder pretraining
                       (sections III.C-E) of a chip built with temporary
                       decoders: the wave through the frozen encoders, then
                       the training phases on one (encoder, decoder) pair
                       whose target is the representation it encodes.

A chip built with ``conv=`` holds a conv network (DESIGN.md "Conv
stages"): its first layers are convolutions, each one core stepped over
every position of its feature map, with the router's pool after it;
``infer``, ``train_step`` and ``layers`` run it through the same compiled
programs, and its numerics match `core.conv`.

Every call runs one compiled program (`repro.sim.compiled`) over the
placement's padded stage stack: the wave is one ``lax.scan`` over the
stages, and a training step (wave + reversed bwd/update scan) is ONE
donated program.  Aggregation sub-stages (Fig. 14) run inside their
layer's time slot.  Numerics match the constrained reference within float
re-association: `infer` == `core.crossbar.mlp_forward` and `train_step` ==
`core.crossbar.paper_backprop_step` (pinned by ``tests/test_chip_sim.py``),
while the counters reproduce `hw_model`'s analytic time/energy to <= 1%.

Counting conventions (shared with the analytic model, pinned by the
cross-validation contract):
  * an aggregation sub-stage executes inside its layer's slot; its cores
    are billed for every phase of the layer (the model prices
    ``lm.total_cores`` per phase);
  * routed outputs per layer = sub-neuron partials (``row_tiles*fan_out``)
    when fan-in is split, else ``fan_out``; aggregation egress and error
    back-transport are not separately counted (mapper convention V.C);
  * loopback-shared layers execute their stages time-multiplexed on one
    core: placed cores shrink, per-layer execution cost does not.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.crossbar import CORE_COLS, CORE_ROWS, CrossbarSpec
from repro.core.mapping import ConvShape, map_conv_network, map_network
from repro.core import hw_model as hw
from repro.sim import compiled as csim
from repro.sim import spans
from repro.sim.faults import inject_faults, reapply
from repro.sim.noc import NocTracker
from repro.sim.placer import (Placement, StageStacks, build_stage_stacks,
                              place_decoders, place_network, pretrain_path,
                              stage_params)
from repro.sim.report import PhaseCounters, PretrainReport, SimReport


def as_rows(x) -> jax.Array:
    """``x`` as a batch of rows, (M, width).  ``jnp.atleast_2d`` is a
    program launch even on a 2-D array, so a batch that is already 2-D
    passes as it is."""
    return x if np.ndim(x) == 2 else jnp.atleast_2d(x)


class Pretraining:
    """Greedy layer-wise autoencoder pretraining (sections III.C-E) as
    `VirtualChip` and `fabric.ChipPipeline` hold it: the temporary
    decoders as placed cores (`placer.place_decoders`) for the whole
    schedule, and over each hidden layer's path (`placer.pretrain_path`)
    one object of the holder's own class (`_over`), built once.  A path's
    stages alias the holder's, so its pulse updates land in the network's
    encoders, and `layers()` hands them to supervised fine-tuning."""

    def _hold_decoders(self, decoders: list[dict] | None) -> None:
        pl = self.placement
        self._decoders = None if decoders is None else place_decoders(
            decoders, pl.dims, pl.rows, pl.cols)
        self._paths: dict = {}        # layer -> object over its path

    def _pretrain_path(self, layer: int):
        """The object over hidden layer ``layer``'s pretraining path
        (encoders ``0 .. layer``, decoder ``layer``); its counters are the
        layer's."""
        if self._decoders is None:
            raise ValueError("pretraining needs an object built with "
                             "decoders=")
        if layer not in self._paths:
            self._paths[layer] = self._over(
                pretrain_path(self.placement, self._decoders, layer),
                f"{self.name}.pretrain{layer}")
        return self._paths[layer]

    def _stale(self, but: int | None = None) -> None:
        """Invalidate the cached envelopes of every path but layer
        ``but``'s: their encoder stages moved."""
        for k, path in self._paths.items():
            if k != but:
                path._moved()

    def _pretrained(self, layer: int) -> None:
        """After a step of layer ``layer``: invalidate every cached
        envelope that holds encoder ``layer`` besides the writer's own."""
        self._moved()
        self._stale(but=layer)

    def decoders(self) -> list[dict[str, jax.Array]]:
        """The temporary decoders, as per-layer dicts."""
        if self._decoders is None:
            return []
        return [stage_params(st) for st in self._decoders]


class VirtualChip(Pretraining):
    """A placed network executing on the simulated core grid.

    Given ``decoders`` (one temporary decoder per hidden layer, layer
    ``j``'s of shape ``(dims[j+1], dims[j])``), the chip also holds them
    as placed cores (`placer.place_decoders`) for the whole greedy
    schedule of `pretrain_step`.

    Given ``conv`` (a `mapping.ConvShape` per leading conv layer), the
    first ``len(conv)`` of ``layers`` are the conv kernels, layer ``l``'s
    of shape ``(k*k*C_in, C_out)``, and the rest the dense chain after
    the last pooled map (`mapping.map_conv_network`)."""

    def __init__(self, layers: list[dict[str, jax.Array]],
                 spec: CrossbarSpec | None = None, *,
                 rows: int = CORE_ROWS, cols: int = CORE_COLS,
                 name: str = "app", share_small_layers: bool = False,
                 input_bits: int = 8,
                 placement: Placement | None = None,
                 faults=None, decoders: list[dict] | None = None,
                 conv: list[ConvShape] | None = None):
        if spec is None:
            from repro.configs.paper_apps import PAPER_SPEC
            spec = PAPER_SPEC
        if spec.split_activation:
            raise NotImplementedError(
                "the virtual chip implements exact aggregation only "
                "(split_activation=False); see DESIGN.md 'Virtual chip'")
        self.spec = spec
        self.name = name
        self.input_bits = input_bits
        if conv and (share_small_layers or decoders is not None
                     or faults is not None):
            raise NotImplementedError(
                "a conv network places every layer on cores of its own, "
                "trains supervised and models no device faults")
        if placement is None:
            dense = layers[len(conv or ()):]
            dims = [int(dense[0]["g_plus"].shape[0])] + \
                   [int(p["g_plus"].shape[1]) for p in dense]
            nmap = (map_conv_network(list(conv), dims, rows, cols) if conv
                    else map_network(dims, rows, cols,
                                     share_small_layers=share_small_layers))
            placement = place_network(layers, nmap, rows, cols)
        self.faults = None
        if faults is not None and not faults.is_null:
            placement = inject_faults(placement, faults, w_max=spec.w_max)
            self.faults = faults
        self.placement = placement
        if decoders is not None and (share_small_layers
                                     or self.faults is not None):
            raise NotImplementedError(
                "pretraining places every layer on cores of its own and "
                "models no device faults")
        self._hold_decoders(decoders)
        self._stacks: StageStacks | None = None   # compiled-path envelope
        self._held: dict = {}         # name -> (value, device copy)
        self.infer_counters = PhaseCounters(
            noc=NocTracker(slot_cycles=placement.cols))
        self.train_counters = PhaseCounters(
            noc=NocTracker(slot_cycles=placement.cols))

    # ------------------------------------------------------------------
    # Compiled whole-step executor (repro.sim.compiled, DESIGN.md §8)
    # ------------------------------------------------------------------

    def _get_stacks(self) -> StageStacks:
        """The padded stage stack, rebuilt whenever the placement's
        conductances were written outside the compiled step (version
        bump: fault injection or re-assert, farm scatter)."""
        if (self._stacks is None
                or self._stacks.built_version != self.placement.version):
            self._stacks = build_stage_stacks(self.placement)
        return self._stacks

    def _on_device(self, name: str, v):
        """The numpy scalar ``v`` as the compiled programs take it, placed
        on the device once for each value: a call that passes ``name``
        the value it passed last moves nothing to the device."""
        held = self._held.get(name)
        if held is None or held[0] != v:
            held = self._held[name] = (v, jax.device_put(v))
        return held[1]

    def _keep(self, st: StageStacks, out, cfg: "csim.ChipConfig") -> None:
        """Keep training result ``out``: its stacks become the envelope the
        next step donates, and the placement's stages take the per-stage
        views (`compiled.kept_views`).  A chip that owns faults then
        re-asserts its stuck masks — pulse updates cannot move a stuck
        device — which replaces the placement with a newer version, so
        the next call rebuilds the envelope from the masked stacks."""
        st.g_plus, st.g_minus = out[0], out[1]
        st.scatter_back(self.placement, *csim.kept_views(out, cfg))
        self._stale()
        if self.faults is not None:
            self.placement = reapply(self.placement, self.faults,
                                     w_max=self.spec.w_max)

    @property
    def _cfg(self) -> "csim.ChipConfig":
        return csim.chip_config(self._get_stacks(), self.spec)

    def _apply_fwd_counters(self, counters: PhaseCounters | None,
                            fcnt, M: int) -> None:
        """Fold the scan's traced fwd accumulators into `PhaseCounters` —
        ONE device->host transfer — and replay the static per-stage NoC
        records (the placement's compile-time routing schedule)."""
        if counters is None:
            return
        with spans.phase(spans.SYNC):
            slots, steps, *conv = (int(v) for v in np.asarray(fcnt))
        with spans.phase(spans.ACCOUNT):
            counters.slots["fwd"] += slots
            counters.core_steps["fwd"] += steps
            st = self._get_stacks()
            routed = list(st.routed)
            for l in range(len(st.conv)):
                c_steps, codes, pooled = conv[3 * l:3 * l + 3]
                counters.record_conv(l, c_steps, codes, pooled,
                                     st.Ts[l] * M)
                routed[l] = (codes + pooled) // M
            for s in range(st.S):
                counters.noc.record(self.placement.stages[s].index,
                                    routed[s], st.Ts[s], M)

    @staticmethod
    def _apply_bwd_counters(counters: PhaseCounters | None, bcnt) -> None:
        if counters is None:
            return
        with spans.phase(spans.SYNC):
            b_slots, b_steps, u_slots, u_steps = (int(v) for v
                                                  in np.asarray(bcnt))
        with spans.phase(spans.ACCOUNT):
            counters.slots["bwd"] += b_slots
            counters.core_steps["bwd"] += b_steps
            counters.slots["update"] += u_slots
            counters.core_steps["update"] += u_steps

    @spans.entry("chip.forward_wave")
    def forward_wave(self, x: jax.Array, *, count: bool = True,
                     train: bool = False, quantize_tail: bool = False
                     ) -> tuple[list[jax.Array], list[jax.Array], jax.Array]:
        """Public wave execution over this chip's stage slice.

        Returns ``(acts, dps, out)``: per-stage input activations, per-stage
        dot products, and the output activation as it leaves the chip —
        tail-quantized when ``quantize_tail`` (the value that rides the
        inter-chip link as 3-bit ADC codes).  ``train=True`` bills the
        training counters instead of the inference counters.  Used by the
        pipeline fabric (``repro.sim.fabric``) to run one chip's slice of a
        split network; :meth:`infer` and :meth:`train_step` are this plus
        the whole-network bookkeeping."""
        counters = None
        if count:
            counters = self.train_counters if train else self.infer_counters
        with spans.phase(spans.PREPARE):
            x = as_rows(x)
            st = self._get_stacks()
            idx, cfg = st.index_pytree(), self._cfg
            tail = self._on_device("tail", np.bool_(quantize_tail))
        with spans.phase(spans.DISPATCH):
            acts, dps, h, fcnt = csim.chip_forward(
                st.g_plus, st.g_minus, x, idx, tail, cfg)
        self._apply_fwd_counters(counters, fcnt, x.shape[0])
        with spans.phase(spans.WRITEBACK):
            return list(acts), list(dps), h

    # ------------------------------------------------------------------
    # Inference
    # ------------------------------------------------------------------

    @spans.entry("chip.infer")
    def infer(self, x: jax.Array, *, count: bool = True) -> jax.Array:
        """One recognition wave (serialized-latency semantics)."""
        counters = self.infer_counters if count else None
        with spans.phase(spans.PREPARE):
            x = jnp.atleast_2d(x)
            st = self._get_stacks()
            idx, cfg = st.index_pytree(), self._cfg
        with spans.phase(spans.DISPATCH):
            out, fcnt = csim.chip_infer(st.g_plus, st.g_minus, x, idx, cfg)
        self._apply_fwd_counters(counters, fcnt, x.shape[0])
        if count:
            with spans.phase(spans.ACCOUNT):
                M = x.shape[0]
                self.infer_counters.samples += M
                self.infer_counters.record_io(
                    self.placement.dims[0] * self.input_bits
                    + self.placement.dims[-1] * hw.ADC_BITS_OUT, M)
        return out

    def infer_stream(self, x: jax.Array) -> tuple[jax.Array, dict]:
        """Pipelined streaming recognition (Fig. 2): sample ``m`` enters
        stage 0 at beat ``m`` while sample ``m-1`` occupies stage 1 — at
        steady state every stage is busy and one sample retires per beat.

        Stages are sample-independent, so the wave execution above computes
        the identical numbers; what changes is the *time* model, derived
        from measured NoC slot counters."""
        out = self.infer(x)
        S = len(self.placement.stages)
        M = x.shape[0] if x.ndim > 1 else 1
        beats = S + M - 1
        stats = {
            "beat_us": self.beat_us,
            "latency_us": S * self.beat_us,
            "makespan_us": beats * self.beat_us,
            "throughput_sps": 1e6 / self.beat_us,
            "occupancy": S * M / (S * beats),
        }
        return out, stats

    @property
    def beat_us(self) -> float:
        """Steady-state pipeline beat: one crossbar evaluation slot plus
        one static routing slot (Table IV: 0.27 + 100 cycles @ 200 MHz
        = 0.77 us for the paper geometry)."""
        return hw.FWD_US + self.infer_counters.noc.slot_us

    # ------------------------------------------------------------------
    # Training (the paper's fwd / bwd / update phases, Table II)
    # ------------------------------------------------------------------

    @spans.entry("chip.backward_update")
    def backward_update(self, acts: list[jax.Array], dps: list[jax.Array],
                        delta: jax.Array, lr: float, *,
                        global_batch: int | None = None,
                        counters: PhaseCounters | None = None,
                        first: int = 0) -> jax.Array:
        """Run the bwd + update phases over this chip's stage slice.

        ``delta`` is the error arriving at the slice's OUTPUT side — the
        global ``target - out`` for the last chip, or the error handed back
        over the inter-chip link by the downstream chip (the pipeline
        fabric's 8-bit sign-magnitude boundary rule holds because the first
        thing each stage iteration does is the III.F step-1 error
        quantization, exactly as in the serial loop).  Returns the error to
        propagate upstream (the value that would cross the link toward the
        previous chip).  ``global_batch`` is the learning-rate batch
        normalizer, the FULL step batch when this chip is a pipeline slice
        (defaults to ``delta``'s batch).  ``first`` freezes the slice's
        stages below it (`compiled.chip_backward`); the returned error is
        then the one entering stage ``first``."""
        M = delta.shape[0]
        B = M if global_batch is None else global_batch
        c = counters if counters is not None else self.train_counters
        with spans.phase(spans.PREPARE):
            st = self._get_stacks()
            idx, cfg = st.index_pytree(), self._cfg
            acts_p, dps_p, delta_p = csim.pad_wave(acts, dps, delta, cfg)
            lr_eff = self._on_device("lr_eff", np.float32(float(lr) / B))
        with spans.phase(spans.DISPATCH):
            out = csim.chip_backward(
                st.g_plus, st.g_minus, acts_p, dps_p, delta_p, idx, cfg,
                lr_eff=lr_eff, first=first)
        with spans.phase(spans.WRITEBACK):
            delta_prev, bcnt = out[2:]
            self._keep(st, out, cfg)
        self._apply_bwd_counters(c, bcnt)
        return delta_prev

    @spans.entry("chip.train_step")
    def train_step(self, x: jax.Array, target: jax.Array,
                   lr: float) -> jax.Array:
        """One stochastic-BP step executed on the chip, writing the pulse
        updates into the conductance stacks in place.  Matches
        `core.crossbar.paper_backprop_step` exactly under equal specs.
        Returns the output error (target - prediction)."""
        with spans.phase(spans.PREPARE):
            x = jnp.atleast_2d(x)
            target = jnp.atleast_2d(target)
        return self._train_program(
            x, target, float(lr) / x.shape[0],
            2 * self.placement.dims[0] * self.input_bits
            + self.placement.dims[-1] * hw.ADC_BITS_OUT)

    def _train_program(self, x, target, lr_eff, io_bits: int,
                       first: int = 0) -> jax.Array:
        """One training step on this chip's envelope: the wave and the
        reversed bwd/update scan are ONE donated XLA program
        (`compiled.chip_train`, ``first`` and ``target=None`` as it takes
        them); the conductance stacks update in place and the counters
        come back in one transfer (DESIGN.md §8).  Bills the training
        counters, ``io_bits`` of off-chip IO a sample; returns the
        error."""
        c = self.train_counters
        M = x.shape[0]
        with spans.phase(spans.PREPARE):
            st = self._get_stacks()
            idx, cfg = st.index_pytree(), self._cfg
        with spans.phase(spans.DISPATCH):
            out = csim.chip_train(st.g_plus, st.g_minus, x, target, idx, cfg,
                                  lr_eff=lr_eff, first=first)
        with spans.phase(spans.WRITEBACK):
            err, fcnt, bcnt = out[2:]
            self._keep(st, out, cfg)
        self._apply_fwd_counters(c, fcnt, M)
        self._apply_bwd_counters(c, bcnt)
        with spans.phase(spans.ACCOUNT):
            c.samples += M
            c.record_io(io_bits, M)
        return err

    # ------------------------------------------------------------------
    # Layer-wise pretraining (sections III.C-E)
    # ------------------------------------------------------------------

    def _over(self, placement: Placement, name: str) -> "VirtualChip":
        return VirtualChip([], self.spec, name=name,
                           input_bits=self.input_bits, placement=placement)

    def _moved(self) -> None:
        """The placement's conductances moved outside this chip's own
        steps: the next call rebuilds the envelope."""
        self.placement.version += 1

    @spans.entry("chip.pretrain_step")
    def pretrain_step(self, layer: int, x: jax.Array,
                      lr: float) -> jax.Array:
        """One greedy pretraining step of hidden layer ``layer`` on the
        batch ``x`` of network inputs, in ONE donated program
        (`compiled.chip_train` with ``first=layer`` and the autoencoder
        target): the wave through the frozen encoders ``0 .. layer-1``
        (the network input DAC-driven, 3-bit codes after), encoder
        ``layer`` and its decoder, whose target is the representation
        that entered encoder ``layer``; the pulse updates go into encoder
        ``layer`` and decoder ``layer`` only, the frozen stacks stay
        bitwise as they were.  Matches `core.autoencoder` pretraining
        (``_ae_bp`` on the reference representation).  Returns the
        reconstruction error (target - reconstruction)."""
        path = self._pretrain_path(layer)
        with spans.phase(spans.PREPARE):
            x = as_rows(x)
            lr_eff = self._on_device("lr_eff",
                                     np.float32(float(lr) / x.shape[0]))
        # IO: the input in and the reconstruction out; the target is on
        # the chip
        err = path._train_program(
            x, None, lr_eff, self.placement.dims[0] * self.input_bits
            + self.placement.dims[layer] * hw.ADC_BITS_OUT, first=layer)
        with spans.phase(spans.ACCOUNT):
            self._pretrained(layer)
        return err

    # ------------------------------------------------------------------
    # Reporting
    # ------------------------------------------------------------------

    def layers(self) -> list[dict[str, jax.Array]]:
        """Current conductances as per-layer dicts (post-training; after
        pretraining, the encoders, as supervised fine-tuning takes
        them)."""
        return self.placement.extract_params()

    def report(self) -> SimReport:
        """Measured per-sample costs from this chip's counters (the
        quantities `hw_model.network_cost` cross-validates, §5.3)."""
        inf, tr = self.infer_counters, self.train_counters
        return SimReport(
            name=self.name,
            dims=self.placement.dims,
            cores=self.placement.n_cores,
            infer_samples=inf.samples,
            train_samples=tr.samples,
            infer_time_us=inf.time_us() if inf.samples else 0.0,
            infer_energy_j=inf.core_energy_j() if inf.samples else 0.0,
            infer_io_j=inf.io_energy_j() if inf.samples else 0.0,
            train_time_us=tr.time_us() if tr.samples else 0.0,
            train_energy_j=(tr.core_energy_j(include_ctrl=True)
                            if tr.samples else 0.0),
            train_io_j=tr.io_energy_j() if tr.samples else 0.0,
            beat_us=self.beat_us,
            throughput_sps=1e6 / self.beat_us,
            routed_per_sample=(
                inf.noc.routed_outputs_per_sample(inf.samples)
                if inf.samples
                else tr.noc.routed_outputs_per_sample(tr.samples)),
            link_utilization=(inf.noc.link_utilization if inf.samples
                              else tr.noc.link_utilization),
            conv_shapes=tuple(c.shape for c in self.placement.nmap.conv),
            conv=(tr if tr.samples else inf).conv_per_sample(),
            pretrain=tuple(self._pretrain_report(k) for k in
                           sorted(self._paths)
                           if self._paths[k].train_counters.samples),
        )

    def _pretrain_report(self, layer: int) -> PretrainReport:
        """Hidden layer ``layer``'s pretraining counters beside the
        analytic cost of its step on this chip's core grid."""
        pl = self.placement
        return PretrainReport.measure(
            layer, [self._paths[layer].train_counters],
            hw.pretrain_step_cost(self.name, list(pl.dims), layer,
                                  rows=pl.rows, cols=pl.cols,
                                  input_bits=self.input_bits))
