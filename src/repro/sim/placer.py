"""Placer: materialize a NetworkMap as stacked per-core conductance arrays.

Each network layer becomes one pipeline *stage* (DESIGN.md "Virtual chip"):

  * the layer's ``row_tiles x col_tiles`` core grid (section V.B) is stored
    as ONE stacked array ``(T, rows, cols)`` with ``T = row_tiles*col_tiles``
    — slice ``t = i*col_tiles + j`` is the physical core holding fan-in tile
    ``i`` of fan-out tile ``j``.  The whole stage executes as a single
    batched Pallas call (`kernels/ops.crossbar_fwd_stacked`), never a Python
    loop over cores;
  * row 0 of the first fan-in tile is the provisioned bias row (Fig. 8).
    The repo's crossbar layers have no bias term, so its conductances start
    at zero and its input line is driven to 0 — the row occupies hardware
    (mapping counts it) but contributes nothing numerically;
  * layers split over fan-in get a Fig.-14 aggregation stage: ``col_tiles``
    cores whose unit-conductance block pattern sums the ``row_tiles``
    sub-neuron partials per neuron.  It too executes as one stacked call.
    The sim implements *exact aggregation* (``split_activation=False``):
    partials cross the NoC at full precision and the activation is applied
    once after aggregation, which is what `crossbar_apply` computes.  Known
    idealization, shared with the mapper: an aggregation core serving
    ``cols`` neurons of fan-in ``row_tiles`` is modeled with
    ``row_tiles*cols`` input lines, which exceeds a physical core's
    ``rows`` inputs once ``row_tiles > rows/cols`` (e.g. the isolet
    2000->1000 layer).  `core/mapping.py` prices exactly this shape
    (``agg_cores = ceil(row_tiles/rows) * col_tiles``), the paper does not
    specify multi-level aggregation, and the sim<->hw_model contract needs
    both sides to count the same chip — so the sim executes what the
    mapper prices.

The placement is mutable state: the virtual chip's update phase writes new
conductance stacks back (`Placement.set_stage_stacks`), and
`Placement.extract_params` slices the stacks back into the per-layer
``{"g_plus", "g_minus"}`` dicts the rest of the repo consumes.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.crossbar import CORE_COLS, CORE_ROWS
from repro.core.mapping import LayerMap, NetworkMap


@dataclasses.dataclass
class Stage:
    """One pipeline stage: a layer's core grid as stacked conductances."""
    index: int
    lmap: LayerMap
    rows: int
    cols: int
    g_plus: jax.Array            # (row_tiles*col_tiles, rows, cols)
    g_minus: jax.Array
    agg_plus: jax.Array | None   # (col_tiles, row_tiles*cols, cols) or None
    agg_minus: jax.Array | None

    @property
    def n_cores(self) -> int:
        """Physical cores executing this stage (main grid + aggregation) —
        measured from the materialized stacks, not copied from the mapper."""
        agg = 0 if self.agg_plus is None else self.agg_plus.shape[0]
        return self.g_plus.shape[0] + agg

    @property
    def row_tiles(self) -> int:
        """Fan-in tiles (sub-neuron splits, Fig. 14) of this stage."""
        return self.lmap.row_tiles

    @property
    def col_tiles(self) -> int:
        """Fan-out tiles of this stage."""
        return self.lmap.col_tiles


@dataclasses.dataclass
class Placement:
    """A placed network: the ordered pipeline stages plus the mapping they
    were materialized from (the sim<->hw_model shared contract)."""
    stages: list[Stage]
    dims: tuple[int, ...]
    rows: int
    cols: int
    nmap: NetworkMap
    version: int = 0      # bumped on every conductance write (cache key for
                          # the compiled executor's padded stage stacks)

    @property
    def n_cores(self) -> int:
        """Placed physical cores.  With loopback sharing, time-multiplexed
        layers occupy the same core, so this is the mapper's placed count
        (the per-stage stacks still execute independently in time)."""
        return self.nmap.cores

    def set_stage_stacks(self, index: int, g_plus: jax.Array,
                         g_minus: jax.Array) -> None:
        """Write updated conductance stacks back into stage ``index`` (the
        virtual chip's update phase mutates the placement in place)."""
        self.stages[index].g_plus = g_plus
        self.stages[index].g_minus = g_minus
        self.version += 1

    def extract_params(self) -> list[dict[str, jax.Array]]:
        """Stacks -> per-layer {"g_plus", "g_minus"} dicts (inverse of
        place_network's tiling, bias row and padding stripped)."""
        out = []
        for st in self.stages:
            F, O = st.lmap.fan_in, st.lmap.fan_out
            r, c = st.row_tiles, st.col_tiles
            gp = _untile(st.g_plus, r, c, st.rows, st.cols)[1:F + 1, :O]
            gm = _untile(st.g_minus, r, c, st.rows, st.cols)[1:F + 1, :O]
            out.append({"g_plus": gp, "g_minus": gm})
        return out


def _tile(g: jax.Array, r: int, c: int, rows: int, cols: int) -> jax.Array:
    """(r*rows, c*cols) padded matrix -> (r*c, rows, cols) core stack."""
    return (g.reshape(r, rows, c, cols).transpose(0, 2, 1, 3)
             .reshape(r * c, rows, cols))


def _untile(stack: jax.Array, r: int, c: int, rows: int,
            cols: int) -> jax.Array:
    return (stack.reshape(r, c, rows, cols).transpose(0, 2, 1, 3)
                 .reshape(r * rows, c * cols))


def _pad_layer(g: jax.Array, r: int, c: int, rows: int,
               cols: int) -> jax.Array:
    """Place a (fan_in, fan_out) matrix into the (r*rows, c*cols) core grid:
    bias row at row 0 (zero conductance), zero-padding elsewhere."""
    F, O = g.shape
    out = jnp.zeros((r * rows, c * cols), g.dtype)
    return out.at[1:F + 1, :O].set(g)


def tile_inputs(x: jax.Array, r: int, c: int, rows: int,
                bias_value: float = 0.0) -> jax.Array:
    """(M, fan_in) activations -> (r*c, M, rows) per-core input slabs.

    Core ``i*c + j`` receives fan-in tile ``i`` (all cores of one fan-in
    tile see the same rows — the routing network fans a neuron output to
    every consuming core).  Row 0 of tile 0 is the bias line, driven at
    ``bias_value`` (0: the repo's layers are bias-free; the row is
    provisioned but silent)."""
    M, F = x.shape
    xb = jnp.concatenate(
        [jnp.full((M, 1), bias_value, x.dtype), x,
         jnp.zeros((M, r * rows - F - 1), x.dtype)], axis=1)
    xt = xb.reshape(M, r, rows).transpose(1, 0, 2)      # (r, M, rows)
    return jnp.repeat(xt, c, axis=0)                    # (r*c, M, rows)


def fold_subneuron_partials(ys: jax.Array, st: Stage) -> jax.Array:
    """(C, r*c, M, cols) main-grid outputs of a fan-in-split stage ->
    (C, c, M, r*cols) aggregation-core input lines (Fig. 14: partial ``i``
    of neuron ``n`` drives line ``i*cols + n``)."""
    C, M = ys.shape[0], ys.shape[2]
    r, c = st.row_tiles, st.col_tiles
    return (ys.reshape(C, r, c, M, st.cols).transpose(0, 2, 3, 1, 4)
              .reshape(C, c, M, r * st.cols))


def stage_dp_from_outputs(ys: jax.Array, st: Stage,
                          agg_out: jax.Array | None = None) -> jax.Array:
    """Core outputs -> (C, M, fan_out) stage dot products.

    ``ys`` is the (C, r*c, M, cols) main-grid output; fan-in-split stages
    pass the (C, c, M, cols) aggregation output instead of summing."""
    C, M = ys.shape[0], ys.shape[2]
    r, c = st.row_tiles, st.col_tiles
    if st.row_tiles > 1:
        dp = agg_out.transpose(0, 2, 1, 3).reshape(C, M, c * st.cols)
    else:
        dp = (ys.reshape(C, r, c, M, st.cols).sum(axis=1)
                .transpose(0, 2, 1, 3).reshape(C, M, c * st.cols))
    return dp[..., :st.lmap.fan_out]


def stage_dot_products(st: Stage, h: jax.Array, g_plus: jax.Array,
                       g_minus: jax.Array, run_fwd) -> jax.Array:
    """One stage's exact-aggregated dot products — with the two reshape
    helpers above, the single owner of the tile/aggregate discipline,
    shared by the serial chip, the farm wave paths, and (helpers only)
    the farm serving beat, so their numerics cannot drift apart.

    ``h`` is ``(M, fan_in)`` or chip-stacked ``(C, Mc, fan_in)``;
    ``g±`` match (``(T, rows, cols)`` / ``(C, T, rows, cols)``).
    ``run_fwd(xs, gp, gm)`` is the stacked forward dispatch (the farm
    passes its shard_mapped variant).  Fan-in-split stages run the
    Fig.-14 aggregation as a second dispatch in the same time slot."""
    chipped = h.ndim == 3
    if not chipped:
        h, g_plus, g_minus = h[None], g_plus[None], g_minus[None]
    r, c = st.row_tiles, st.col_tiles
    C = h.shape[0]
    xs = jax.vmap(lambda hh: tile_inputs(hh, r, c, st.rows))(h)
    ys = run_fwd(xs, g_plus, g_minus)
    agg_out = None
    if r > 1:
        # sub-neuron partials cross the NoC to the aggregation cores,
        # which sum them through unit conductances.
        u = fold_subneuron_partials(ys, st)
        agg_p = jnp.broadcast_to(st.agg_plus, (C,) + st.agg_plus.shape)
        agg_m = jnp.broadcast_to(st.agg_minus, (C,) + st.agg_minus.shape)
        agg_out = run_fwd(u, agg_p, agg_m)
    dp = stage_dp_from_outputs(ys, st, agg_out)
    return dp if chipped else dp[0]


def untile_outputs(ys: jax.Array, r: int, c: int, fan_out: int) -> jax.Array:
    """(r*c, M, cols) per-core partial DPs -> (M, fan_out) exact-aggregated
    dot products (sum over fan-in tiles, concat over fan-out tiles)."""
    T, M, cols = ys.shape
    part = ys.reshape(r, c, M, cols).sum(axis=0)        # (c, M, cols)
    return part.transpose(1, 0, 2).reshape(M, c * cols)[:, :fan_out]


def _agg_pattern(r: int, cols: int, dtype) -> jax.Array:
    """Unit-conductance block pattern of one aggregation core: input line
    ``i*cols + n`` (sub-neuron partial i of neuron n) feeds neuron n."""
    eye = jnp.eye(cols, dtype=dtype)
    return jnp.tile(eye, (r, 1))                        # (r*cols, cols)


def sub_placement(pl: Placement, stage_indices: tuple[int, ...]) -> Placement:
    """A contiguous slice of a placement as its own (sub-)chip placement.

    The pipeline fabric (``repro.sim.fabric``) splits one placed network
    into per-chip stage groups; each group becomes a `Placement` whose
    stage list ALIASES the parent's `Stage` objects — a chip slice's pulse
    updates write into the same stacks the parent placement (and therefore
    `Placement.extract_params` on the full network) sees.  The sub-map
    re-derives placed cores / routed outputs for the slice so per-chip
    accounting stays measured, not copied."""
    if list(stage_indices) != list(range(stage_indices[0],
                                         stage_indices[-1] + 1)):
        raise ValueError(f"stage group {stage_indices} is not contiguous")
    stages = [pl.stages[i] for i in stage_indices]
    lms = tuple(pl.nmap.layers[i] for i in stage_indices)
    routed = sum(lm.routed_outputs for lm in lms)
    sub_nmap = NetworkMap(layers=lms,
                          cores=sum(lm.placed_cores for lm in lms),
                          routed_outputs=routed, routing_cycles=routed)
    dims = (lms[0].fan_in,) + tuple(lm.fan_out for lm in lms)
    return Placement(stages=stages, dims=dims, rows=pl.rows, cols=pl.cols,
                     nmap=sub_nmap)


# ---------------------------------------------------------------------------
# StageStacks: the padded ragged stage stack of the compiled executor
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StageStacks:
    """All stages of a placement padded to one (T_max, rows, cols) envelope.

    The compiled whole-step executor (``repro.sim.compiled``, DESIGN.md §8)
    runs the stage loop as a single ``lax.scan``, which needs every
    per-stage operand to share one static shape.  This container owns that
    padding/mask layout:

      * ``g_plus``/``g_minus`` — ``(S, T_max, rows, cols)`` conductance
        stacks; cores beyond a stage's ``row_tiles*col_tiles`` grid are
        zero (a zero crossbar emits zeros, which the gathers below never
        read back into a valid lane);
      * gather maps (int32, precomputed host-side) that express the
        per-stage tile/aggregate/fold discipline of `tile_inputs`,
        `_tile_cols`, `stage_dp_from_outputs` and the backward fan-in fold
        as shape-uniform indexed reads.  Every index either addresses a
        valid element or a dedicated always-zero slot, so the SAME traced
        program executes any stage of the ragged stack;
      * ``valid_out`` — ``(S, N_pad)`` output-lane validity, re-masked
        after the transport ADC (quantizing a padded zero lane would emit
        a nonzero code — the mask keeps padding lanes exactly zero so
        ragged padding is bitwise-invisible, the §8 invariance the
        pipeline fabric's bitwise pins rest on).

    Ragged reductions over the padded axes (the fan-in-tile aggregation of
    Fig. 14 and the backward fan-out fold) are evaluated as SEQUENTIAL
    left-to-right sums over the static maxima: trailing zero terms are
    exact no-ops in float addition, so a stage computes bit-identical
    values no matter how large an envelope it is embedded in — a chip
    slice's stacks and the full network's stacks agree bitwise.
    """
    S: int
    T_max: int
    r_max: int
    c_max: int
    rows: int
    cols: int
    L: int               # padded input-vector length (bias slot 0 + lanes)
    N_pad: int           # padded output-lane count (max col_tiles*cols)
    out_dim: int         # fan_out of the last stage
    Ts: tuple[int, ...]            # per-stage cores = emitting links
    fan_in: tuple[int, ...]
    fan_out: tuple[int, ...]
    n_cores: tuple[int, ...]       # per-stage billed cores (grid + agg)
    routed: tuple[int, ...]        # per-stage routed outputs (NoC record)
    g_plus: jax.Array              # (S, T_max, rows, cols)
    g_minus: jax.Array
    in_idx: jax.Array              # (S, T_max, rows)  h_ext -> core lines
    ds_idx: jax.Array              # (S, T_max, cols)  local_ext -> core cols
    dp_idx: jax.Array              # (S, r_max, N_pad) ys_flat_ext -> dp lanes
    fold_idx: jax.Array            # (S, r_max, c_max) dxs_ext core pick
    prev_idx: jax.Array            # (S, N_pad)        dxg_flat_ext -> delta
    valid_out: jax.Array           # (S, N_pad) float32 {0, 1}
    core_counts: jax.Array         # (S,) int32 (traced counter feed)
    built_version: int = -1

    def index_pytree(self) -> dict[str, jax.Array]:
        """The traced (non-donated) operands of the compiled programs."""
        return {"in_idx": self.in_idx, "ds_idx": self.ds_idx,
                "dp_idx": self.dp_idx, "fold_idx": self.fold_idx,
                "prev_idx": self.prev_idx, "valid_out": self.valid_out,
                "core_counts": self.core_counts}

    def scatter_back(self, pl: "Placement", views_plus, views_minus) -> None:
        """Hand the compiled step's per-stage views (`compiled.kept_views`
        of a ``chip_train``/``chip_backward`` result: the updated stacks
        already cut to each stage's cores) to the placement's `Stage`
        objects and mark the placement clean.  Plain assignment, no device work; the aliasing
        contract of `sub_placement` keeps holding because the Stage
        objects themselves are updated in place."""
        for st, gp, gm in zip(pl.stages, views_plus, views_minus):
            st.g_plus, st.g_minus = gp, gm
        pl.version += 1
        self.built_version = pl.version


def build_stage_stacks(pl: Placement) -> StageStacks:
    """Pad a placement's ragged stage list into a `StageStacks` envelope.

    Index-map construction happens in numpy (static, host-side); only the
    conductance stacks and the final index arrays land on device."""
    import numpy as np

    stages = pl.stages
    S = len(stages)
    rows, cols = pl.rows, pl.cols
    rs = [st.row_tiles for st in stages]
    cs = [st.col_tiles for st in stages]
    Ts = [r * c for r, c in zip(rs, cs)]
    T_max, r_max, c_max = max(Ts), max(rs), max(cs)
    fan_in = tuple(st.lmap.fan_in for st in stages)
    fan_out = tuple(st.lmap.fan_out for st in stages)
    # output-lane envelope: wide enough for every stage's fan-out tiling
    # AND every stage's fan-in (the upstream error delta rides the same
    # lanes on the way back, and stage 0's fan-in can exceed any fan-out)
    N_pad = max(max(c * cols for c in cs), max(fan_in))
    L = 1 + N_pad

    gp = jnp.zeros((S, T_max, rows, cols), jnp.float32)
    gm = jnp.zeros((S, T_max, rows, cols), jnp.float32)
    for s, st in enumerate(stages):
        gp = gp.at[s, :Ts[s]].set(st.g_plus.astype(jnp.float32))
        gm = gm.at[s, :Ts[s]].set(st.g_minus.astype(jnp.float32))

    in_idx = np.zeros((S, T_max, rows), np.int32)       # 0 = bias slot (=0)
    ds_idx = np.full((S, T_max, cols), N_pad, np.int32)  # N_pad = zero col
    dp_idx = np.full((S, r_max, N_pad), T_max * cols, np.int32)
    fold_idx = np.full((S, r_max, c_max), T_max, np.int32)
    prev_idx = np.full((S, N_pad), r_max * rows, np.int32)
    valid = np.zeros((S, N_pad), np.float32)
    for s in range(S):
        r, c, F, O = rs[s], cs[s], fan_in[s], fan_out[s]
        t = np.arange(Ts[s])
        # input tiling (tile_inputs): core i*c+j line l <- global line
        # i*rows + l of [bias, x, zeros]; lines past the payload stay on
        # the always-zero bias slot.
        g = (t[:, None] // c) * rows + np.arange(rows)[None, :]
        in_idx[s, :Ts[s]] = np.where((g >= 1) & (g <= F), g, 0)
        # fan-out tiling (_tile_cols): core i*c+j col k <- lane j*cols+k of
        # the local error (zero beyond fan_out by construction).
        ds_idx[s, :Ts[s]] = ((t[:, None] % c) * cols
                             + np.arange(cols)[None, :])
        # dp assembly: lane n sums partials ys[(i*c + n//cols)*cols
        # + n%cols] over fan-in tiles i (exact aggregation, Fig. 14).
        n = np.arange(O)
        for i in range(r):
            dp_idx[s, i, :O] = (i * c + n // cols) * cols + n % cols
        # backward fan-in fold: group i sums dxs over its c fan-out tiles.
        fold_idx[s, :r, :c] = (np.arange(r)[:, None] * c
                               + np.arange(c)[None, :])
        # upstream error: lane n <- global line n+1 of the folded dx
        # (strip the bias line), zero beyond this stage's fan_in.
        prev_idx[s, :F] = np.arange(F) + 1
        valid[s, :O] = 1.0

    return StageStacks(
        S=S, T_max=T_max, r_max=r_max, c_max=c_max, rows=rows, cols=cols,
        L=L, N_pad=N_pad, out_dim=fan_out[-1], Ts=tuple(Ts),
        fan_in=fan_in, fan_out=fan_out,
        n_cores=tuple(st.n_cores for st in stages),
        routed=tuple(st.lmap.routed_outputs for st in stages),
        g_plus=gp, g_minus=gm,
        in_idx=jnp.asarray(in_idx), ds_idx=jnp.asarray(ds_idx),
        dp_idx=jnp.asarray(dp_idx), fold_idx=jnp.asarray(fold_idx),
        prev_idx=jnp.asarray(prev_idx), valid_out=jnp.asarray(valid),
        core_counts=jnp.asarray([st.n_cores for st in stages], jnp.int32),
        built_version=pl.version)


def place_layer(index: int, params: dict[str, jax.Array], lmap: LayerMap,
                rows: int, cols: int) -> Stage:
    """Materialize one layer's conductances as a pipeline `Stage` (core
    stack + Fig.-14 aggregation stack when fan-in is split)."""
    gp, gm = params["g_plus"], params["g_minus"]
    r, c = lmap.row_tiles, lmap.col_tiles
    agg_p = agg_m = None
    if r > 1:
        # Fig. 14 aggregation cores: one per fan-out tile, unit weights.
        pat = _agg_pattern(r, cols, gp.dtype)
        agg_p = jnp.broadcast_to(pat, (c,) + pat.shape)
        agg_m = jnp.zeros_like(agg_p)
    return Stage(
        index=index, lmap=lmap, rows=rows, cols=cols,
        g_plus=_tile(_pad_layer(gp, r, c, rows, cols), r, c, rows, cols),
        g_minus=_tile(_pad_layer(gm, r, c, rows, cols), r, c, rows, cols),
        agg_plus=agg_p, agg_minus=agg_m)


def place_network(layers: list[dict[str, jax.Array]],
                  nmap: NetworkMap | None = None,
                  rows: int = CORE_ROWS, cols: int = CORE_COLS) -> Placement:
    """Materialize per-layer conductance dicts onto the simulated core grid.

    ``nmap`` defaults to the unshared `map_network` placement of the layer
    dims; pass a `map_network(..., share_small_layers=True)` map to model
    loopback packing (same stage execution, fewer placed cores)."""
    dims = [int(layers[0]["g_plus"].shape[0])] + \
           [int(p["g_plus"].shape[1]) for p in layers]
    if nmap is None:
        from repro.core.mapping import map_network
        nmap = map_network(dims, rows, cols)
    if len(nmap.layers) != len(layers):
        raise ValueError(f"NetworkMap has {len(nmap.layers)} layers, "
                         f"params have {len(layers)}")
    stages = []
    for i, (p, lm) in enumerate(zip(layers, nmap.layers)):
        got = tuple(p["g_plus"].shape)
        if got != (lm.fan_in, lm.fan_out):
            raise ValueError(f"layer {i}: params {got} != map "
                             f"({lm.fan_in}, {lm.fan_out})")
        if lm.row_tiles > rows:
            # beyond this the mapper's agg core count (ceil(r/rows) *
            # col_tiles) stops collapsing to col_tiles and the stacks
            # below would disagree with the priced placement.
            raise NotImplementedError(
                f"layer {i}: {lm.row_tiles} fan-in tiles need multi-level "
                f"aggregation, which neither the mapper nor the sim models")
        stages.append(place_layer(i, p, lm, rows, cols))
    return Placement(stages=stages, dims=tuple(dims), rows=rows, cols=cols,
                     nmap=nmap)
