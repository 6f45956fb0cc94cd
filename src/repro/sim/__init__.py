"""repro.sim — an executable multicore chip simulator (virtual chip).

The analytic side of the repo (`core/mapping.py` allocates cores,
`core/hw_model.py` prices them) never *runs* a network as the paper's chip.
This package does: it materializes a :class:`repro.core.mapping.NetworkMap`
placement as stacked per-core conductance arrays, executes inference and the
paper's three training phases (fwd/bwd/update, Table II) as compiled
programs (one ``lax.scan`` over the stages; fused Pallas crossbar kernels
on a TPU), moves neuron outputs between cores through an
8-bit-link NoC model with per-link cycle counters, and reports time/energy
from *measured* counters — cross-validated against `hw_model`'s analytic
numbers (DESIGN.md "Virtual chip").

Modules:
  placer   NetworkMap + layer params -> stacked conductance tiles per stage
           (+ StageStacks, the padded ragged envelope of the compiled step)
  noc      static routing schedule model, per-link cycle/bit counters
  chip     VirtualChip: infer / pipelined streaming / train_step + counters
  compiled jitted whole-step programs: every hot loop (wave, train step,
           farm step, serving beats) as one donated lax.scan (DESIGN.md §8)
  report   SimReport: counters -> time/energy, hw_model cross-validation
  faults   memristor stuck-on/stuck-off masks + per-core variation injection
  cluster  ChipFarm / FarmServer: N-chip data-parallel farm + serving
           front-end, host-link accounting (DESIGN.md §6)
  fabric   ChipPipeline / PipelineServer / PipelineFarm: pipeline-parallel
           fabric for networks larger than one chip, inter-chip link
           accounting (DESIGN.md §7)
  spans    host spans of each public call and its phases (prepare,
           dispatch, writeback, sync, account) for the profiler's trace
"""
from repro.sim.chip import VirtualChip  # noqa: F401
from repro.sim.cluster import ChipFarm, FarmServer, build_farm  # noqa: F401
from repro.sim.fabric import (ChipPipeline, PipelineFarm,  # noqa: F401
                              PipelineServer, build_pipeline)
from repro.sim.faults import inject_faults  # noqa: F401
from repro.sim.placer import (Placement, StageStacks,  # noqa: F401
                              build_stage_stacks, place_network)
from repro.sim.report import (FarmReport, PipelineReport,  # noqa: F401
                              SimReport)
