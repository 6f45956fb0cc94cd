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
                       outer product written into the stacks in place).

Every stage executes as ONE batched Pallas call over its core stack
(`kernels/ops.crossbar_fwd_stacked` and friends); aggregation sub-stages
(Fig. 14) run inside their layer's time slot.  Numerics match the
constrained reference exactly: `infer` == `core.crossbar.mlp_forward` and
`train_step` == `core.crossbar.paper_backprop_step` (pinned by
``tests/test_chip_sim.py``), while the counters reproduce `hw_model`'s
analytic time/energy to <= 1%.

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

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quantization as q
from repro.core.crossbar import (CORE_COLS, CORE_ROWS, F32, CrossbarSpec,
                                 hard_sigmoid, hard_sigmoid_deriv)
from repro.core.mapping import map_network
from repro.core import hw_model as hw
from repro.kernels import ops as kernel_ops
from repro.sim import compiled as csim
from repro.sim import spans
from repro.sim.noc import NocTracker
from repro.sim.placer import (Placement, Stage, StageStacks,
                              build_stage_stacks, place_network,
                              stage_dot_products, tile_inputs)
from repro.sim.report import PhaseCounters, SimReport


def compiled_enabled() -> bool:
    """Whether the compiled whole-step executor is active (DESIGN.md §8).
    ``REPRO_SIM_COMPILED=0`` falls back to the eager per-stage reference
    path everywhere (the differential baseline)."""
    return os.environ.get("REPRO_SIM_COMPILED", "1") != "0"


def as_rows(x) -> jax.Array:
    """``x`` as a batch of rows, (M, width).  ``jnp.atleast_2d`` is a
    program launch even on a 2-D array, so a batch that is already 2-D
    passes as it is."""
    return x if np.ndim(x) == 2 else jnp.atleast_2d(x)


def _tile_cols(v: jax.Array, r: int, c: int, cols: int) -> jax.Array:
    """(M, fan_out) per-neuron values -> (r*c, M, cols) per-core slabs
    (slice t = i*c + j carries fan-out tile j, same for every fan-in i)."""
    M, O = v.shape
    vp = jnp.pad(v, ((0, 0), (0, c * cols - O)))
    ct = vp.reshape(M, c, cols).transpose(1, 0, 2)      # (c, M, cols)
    return jnp.tile(ct, (r, 1, 1))


class VirtualChip:
    """A placed network executing on the simulated core grid."""

    def __init__(self, layers: list[dict[str, jax.Array]],
                 spec: CrossbarSpec | None = None, *,
                 rows: int = CORE_ROWS, cols: int = CORE_COLS,
                 name: str = "app", share_small_layers: bool = False,
                 input_bits: int = 8,
                 placement: Placement | None = None,
                 faults=None):
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
        if placement is None:
            dims = [int(layers[0]["g_plus"].shape[0])] + \
                   [int(p["g_plus"].shape[1]) for p in layers]
            nmap = map_network(dims, rows, cols,
                               share_small_layers=share_small_layers)
            placement = place_network(layers, nmap, rows, cols)
        self.faults = None
        if faults is not None and not faults.is_null:
            from repro.sim.faults import inject_faults
            placement = inject_faults(placement, faults, w_max=spec.w_max)
            self.faults = faults
        self.placement = placement
        self._stacks: StageStacks | None = None   # compiled-path envelope
        self._held: dict = {}         # name -> (value, device copy)
        self.infer_counters = PhaseCounters(
            noc=NocTracker(slot_cycles=placement.cols))
        self.train_counters = PhaseCounters(
            noc=NocTracker(slot_cycles=placement.cols))

    # ------------------------------------------------------------------
    # Compiled whole-step executor (repro.sim.compiled, DESIGN.md §8)
    # ------------------------------------------------------------------

    def _compiled_active(self) -> bool:
        """Compiled path applies unless disabled or the chip owns faults
        (the stuck-mask re-assert mutates stacks mid-step — that path
        stays on the eager reference)."""
        return compiled_enabled() and self.faults is None

    def _get_stacks(self) -> StageStacks:
        """The padded stage stack, rebuilt whenever the placement's
        conductances were written outside the compiled step (version
        bump: eager updates, fault injection, farm scatter)."""
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
            slots, steps = (int(v) for v in np.asarray(fcnt))
        with spans.phase(spans.ACCOUNT):
            counters.slots["fwd"] += slots
            counters.core_steps["fwd"] += steps
            st = self._get_stacks()
            for s in range(st.S):
                counters.noc.record(self.placement.stages[s].index,
                                    st.routed[s], st.Ts[s], M)

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

    # ------------------------------------------------------------------
    # Stage execution (one batched Pallas call per stage)
    # ------------------------------------------------------------------

    def _stage_dp(self, st: Stage, h: jax.Array) -> jax.Array:
        """Run one stage's core stack on a (M, fan_in) input wave; returns
        the exact-aggregated (M, fan_out) dot products.  The tile /
        Fig.-14 aggregation discipline lives in `placer.stage_dot_products`
        (shared with the farm)."""
        return stage_dot_products(st, h, st.g_plus, st.g_minus,
                                  kernel_ops.crossbar_fwd_stacked)

    def _count_stage(self, counters: PhaseCounters, st: Stage,
                     samples: int) -> None:
        """Measured fwd accounting for one stage execution: one time slot
        on the stacks' core count, plus the stage's NoC egress."""
        counters.record_phase("fwd", st.n_cores, samples)
        links = st.g_plus.shape[0]           # one outbound link per core
        counters.noc.record(st.index, st.lmap.routed_outputs, links,
                            samples)

    def _forward(self, x: jax.Array, counters: PhaseCounters | None, *,
                 quantize_tail: bool = False
                 ) -> tuple[list[jax.Array], list[jax.Array], jax.Array]:
        """Wave through all stages; returns (per-stage inputs, DPs, output
        activation) with the reference path's transport semantics: the
        network input is DAC-driven (no ADC), inter-stage activations are
        3-bit quantized, and the last stage's output leaves raw for the
        training unit — unless ``quantize_tail`` is set, in which case the
        tail activation is ADC-quantized too (this chip is a mid-pipeline
        slice and its output crosses an inter-chip link, DESIGN.md §7)."""
        acts, dps = [], []
        h = x
        last = len(self.placement.stages) - 1
        for si, st in enumerate(self.placement.stages):
            acts.append(h)
            dp = self._stage_dp(st, h)
            dps.append(dp)
            if counters is not None:
                self._count_stage(counters, st, x.shape[0])
            h = hard_sigmoid(dp)
            if (si < last or quantize_tail) and self.spec.transport_quant:
                h = q.adc_quantize_ste(h, self.spec.adc_bits)
        return acts, dps, h

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
        if not self._compiled_active():
            return self._forward(as_rows(x), counters,
                                 quantize_tail=quantize_tail)
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
        if self._compiled_active():
            with spans.phase(spans.PREPARE):
                x = jnp.atleast_2d(x)
                st = self._get_stacks()
                idx, cfg = st.index_pytree(), self._cfg
            with spans.phase(spans.DISPATCH):
                out, fcnt = csim.chip_infer(st.g_plus, st.g_minus, x, idx,
                                            cfg)
            self._apply_fwd_counters(counters, fcnt, x.shape[0])
        else:
            x = jnp.atleast_2d(x)
            _, dps, _ = self._forward(x, counters)
            out = hard_sigmoid(dps[-1])
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
                        counters: PhaseCounters | None = None) -> jax.Array:
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
        (defaults to ``delta``'s batch)."""
        spec = self.spec
        M = delta.shape[0]
        B = M if global_batch is None else global_batch
        c = counters if counters is not None else self.train_counters

        if self._compiled_active():
            with spans.phase(spans.PREPARE):
                st = self._get_stacks()
                idx, cfg = st.index_pytree(), self._cfg
                acts_p, dps_p, delta_p = csim.pad_wave(acts, dps, delta, cfg)
                lr_eff = self._on_device("lr_eff", np.float32(float(lr) / B))
            with spans.phase(spans.DISPATCH):
                out = csim.chip_backward(
                    st.g_plus, st.g_minus, acts_p, dps_p, delta_p, idx, cfg,
                    lr_eff=lr_eff)
            with spans.phase(spans.WRITEBACK):
                st.g_plus, st.g_minus, delta_prev, bcnt = out
                st.scatter_back(self.placement, *csim.kept_views(out, cfg))
            self._apply_bwd_counters(c, bcnt)
            return delta_prev

        for si in reversed(range(len(self.placement.stages))):
            st = self.placement.stages[si]
            r, ct = st.row_tiles, st.col_tiles
            if spec.error_quant:
                # III.F step 1: errors ride the links as 8-bit
                # sign-magnitude codes.
                delta = q.error_quantize(delta, spec.err_bits).dequantize()
            local = delta * hard_sigmoid_deriv(dps[si])

            # -- backward phase: the error drives the SAME conductance
            # stacks transposed (Eq. 7 / Fig. 9), one batched call.
            ds = _tile_cols(local, r, ct, st.cols)
            dxs = kernel_ops.crossbar_bwd_stacked(ds, st.g_plus, st.g_minus)
            dx = (dxs.reshape(r, ct, M, st.rows).sum(axis=1)
                     .transpose(1, 0, 2).reshape(M, r * st.rows))
            delta_prev = dx[:, 1:st.lmap.fan_in + 1]   # strip bias line
            c.record_phase("bwd", st.n_cores, M)

            # -- update phase: per-core outer product + pulse
            # discretization + clipping, written into the stacks.
            xs = tile_inputs(acts[si], r, ct, st.rows)
            if spec.update_quant:
                gp, gm = kernel_ops.pulse_update_stacked(
                    st.g_plus, st.g_minus, xs, ds, lr=lr / B,
                    max_dw=spec.max_update, levels=spec.update_levels,
                    w_max=spec.w_max)
            else:
                dw = 2.0 * (lr / B) * jnp.einsum("tmk,tmn->tkn", xs, ds,
                                                 precision=F32)
                gp = jnp.clip(st.g_plus + 0.5 * dw, 0.0, spec.w_max)
                gm = jnp.clip(st.g_minus - 0.5 * dw, 0.0, spec.w_max)
            self.placement.set_stage_stacks(si, gp, gm)
            c.record_phase("update", st.n_cores, M)

            delta = delta_prev

        if self.faults is not None:
            # pulse updates cannot move a stuck device: re-assert the
            # masks so training works around, not through, broken cells.
            from repro.sim.faults import reapply
            self.placement = reapply(self.placement, self.faults,
                                     w_max=self.spec.w_max)
        return delta

    @spans.entry("chip.train_step")
    def train_step(self, x: jax.Array, target: jax.Array,
                   lr: float) -> jax.Array:
        """One stochastic-BP step executed on the chip, writing the pulse
        updates into the conductance stacks in place.  Matches
        `core.crossbar.paper_backprop_step` exactly under equal specs.
        Returns the output error (target - prediction)."""
        c = self.train_counters
        if self._compiled_active():
            # the whole step — wave + reversed bwd/update scan — is ONE
            # donated XLA program; the conductance stacks update in place
            # and the counters come back in one transfer (DESIGN.md §8).
            with spans.phase(spans.PREPARE):
                x = jnp.atleast_2d(x)
                target = jnp.atleast_2d(target)
                M = x.shape[0]
                st = self._get_stacks()
                idx, cfg = st.index_pytree(), self._cfg
            with spans.phase(spans.DISPATCH):
                out = csim.chip_train(
                    st.g_plus, st.g_minus, x, target, idx, cfg,
                    lr_eff=float(lr) / M)
            with spans.phase(spans.WRITEBACK):
                st.g_plus, st.g_minus, err, fcnt, bcnt = out
                st.scatter_back(self.placement, *csim.kept_views(out, cfg))
            self._apply_fwd_counters(c, fcnt, M)
            self._apply_bwd_counters(c, bcnt)
        else:
            x = jnp.atleast_2d(x)
            target = jnp.atleast_2d(target)
            M = x.shape[0]
            acts, dps, _ = self._forward(x, c)
            out = hard_sigmoid(dps[-1])
            self.backward_update(acts, dps, target - out, lr, counters=c)
            err = target - out

        with spans.phase(spans.ACCOUNT):
            c.samples += M
            c.record_io(2 * self.placement.dims[0] * self.input_bits
                        + self.placement.dims[-1] * hw.ADC_BITS_OUT, M)
        return err

    # ------------------------------------------------------------------
    # Reporting
    # ------------------------------------------------------------------

    def layers(self) -> list[dict[str, jax.Array]]:
        """Current conductances as per-layer dicts (post-training)."""
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
        )
