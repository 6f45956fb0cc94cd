"""Multicore mapper: assign network layers to 400x100 neural cores.

Implements section V.B "Mapping Neural Networks to Cores":

  * a layer with ``fan_out`` neurons of ``fan_in`` inputs occupies
    ``ceil(fan_in/400) * ceil(fan_out/100)`` cores,
  * fan-in splits add an aggregation stage (Fig. 14): ``fan_out`` aggregation
    neurons each with ``ceil(fan_in/400)`` inputs, packed into cores,
  * layers much smaller than a core may share one core (pipelined through the
    core's routing switch loopback, Fig. 2) — ``share_small_layers=True``
    packs consecutive single-core layers into one physical core while their
    combined rows/columns fit the crossbar (this is how Table III reaches
    1 core for the 41-15-41 anomaly network),
  * routed traffic per layer = fan_out neuron outputs (ADC codes) over 8-bit
    links (section V.C).

The mapper also emits the static routing schedule length (cycles) used by the
hardware model.  This is the compile-time "who sends what when" table that,
at pod scale, becomes the XLA SPMD collective schedule (DESIGN.md section 2).

A convolution layer (`map_conv_layer`, DESIGN.md "Conv stages") is one
core holding the ``k*k*C_in x C_out`` kernel, time-multiplexed: the core
is stepped once per output position of the feature map, so a sample
costs ``positions`` core steps per phase and routes ``positions*C_out``
codes.  The ``pool x pool`` subsampling after it uses no core: the router
sums each window's codes and forwards the sum (`PoolMap`).

A :class:`NetworkMap` is also the placement contract consumed by the
executable virtual chip (``repro.sim``, DESIGN.md "Virtual chip"): the sim
materializes each LayerMap's ``row_tiles x col_tiles`` grid as stacked
conductance arrays and executes them as batched Pallas kernel calls.
"""
from __future__ import annotations

import dataclasses
import math

from repro.core.crossbar import CORE_COLS, CORE_ROWS


@dataclasses.dataclass(frozen=True)
class LayerMap:
    fan_in: int
    fan_out: int
    row_tiles: int          # fan-in splits (sub-neuron groups, Fig. 14)
    col_tiles: int          # fan-out splits
    cores: int              # crossbar cores for the layer itself
    agg_cores: int          # cores implementing the aggregation stage
    routed_outputs: int     # neuron outputs crossing the routing network
    shared: bool = False    # rides in the previous layer's core (loopback,
                            # Fig. 2) — contributes 0 *additional* cores

    @property
    def total_cores(self) -> int:
        """Cores the layer's phases execute on (energy accounting) —
        unchanged by sharing: a shared core runs each resident layer in
        sequence, so per-layer execution cost is identical."""
        return self.cores + self.agg_cores

    @property
    def placed_cores(self) -> int:
        """Additional physical cores the layer occupies (area/core count)."""
        return 0 if self.shared else self.total_cores


@dataclasses.dataclass(frozen=True)
class ConvShape:
    """One convolution layer: ``out`` maps of a ``k x k`` kernel over an
    ``in_shape`` (H, W, C) map, stride 1, no padding, then a ``pool x
    pool`` mean formed in the router.  Maps are flattened row-major,
    channels last; a patch's lanes are ordered (dy, dx, c)."""
    in_shape: tuple[int, int, int]
    k: int
    out: int
    pool: int = 1

    @classmethod
    def from_config(cls, d: dict) -> "ConvShape":
        """From a configuration entry ``{"in": [H, W, C], "k", "out",
        "pool"}``."""
        return cls(tuple(int(v) for v in d["in"]), int(d["k"]),
                   int(d["out"]), int(d.get("pool", 1)))

    @property
    def out_hw(self) -> tuple[int, int]:
        """Output positions of the kernel, (H', W')."""
        h, w, _ = self.in_shape
        return h - self.k + 1, w - self.k + 1

    @property
    def positions(self) -> int:
        """Core steps per sample: one per output position."""
        return self.out_hw[0] * self.out_hw[1]

    @property
    def fan_in(self) -> int:
        """Lanes of one patch, ``k*k*C_in``."""
        return self.k * self.k * self.in_shape[2]

    @property
    def in_size(self) -> int:
        """Lanes of the input map, flattened."""
        return self.in_shape[0] * self.in_shape[1] * self.in_shape[2]

    @property
    def pooled_hw(self) -> tuple[int, int]:
        h, w = self.out_hw
        return h // self.pool, w // self.pool

    @property
    def pooled_size(self) -> int:
        """Lanes handed to the next layer: the pooled map, flattened."""
        h, w = self.pooled_hw
        return h * w * self.out


@dataclasses.dataclass(frozen=True)
class PoolMap:
    """A ``window x window`` subsampling in the router, on no core: per
    sample ``sums`` pooled values, each the sum of ``window**2`` codes,
    each routed on to the next layer's core."""
    window: int
    sums: int


@dataclasses.dataclass(frozen=True)
class ConvLayerMap:
    """A convolution layer placed as one time-multiplexed core
    (``core``, `map_layer` of the kernel) stepped over ``positions``
    output positions a sample, routing ``routed_codes`` codes to the
    router, then its `PoolMap`."""
    shape: ConvShape
    core: LayerMap
    positions: int
    routed_codes: int
    pool: PoolMap

    @property
    def routed_outputs(self) -> int:
        """Per-sample outputs crossing the routing network: the core's
        codes and the pooled values."""
        return self.routed_codes + self.pool.sums


@dataclasses.dataclass(frozen=True)
class NetworkMap:
    layers: tuple[LayerMap, ...]
    cores: int
    routed_outputs: int     # per forward pass
    routing_cycles: int     # 8-bit link, one output per cycle per link
    conv: tuple[ConvLayerMap, ...] = ()   # leading conv layers (their
                                          # stage maps lead ``layers``)

    @property
    def dims(self) -> tuple[int, ...]:
        """Widths at the layer boundaries: the network input, then each
        layer's output as the next layer takes it (a conv layer's pooled
        map, flattened)."""
        n = len(self.conv)
        if not n:
            return (self.layers[0].fan_in,) + tuple(
                lm.fan_out for lm in self.layers)
        return ((self.conv[0].shape.in_size,)
                + tuple(c.shape.pooled_size for c in self.conv)
                + tuple(lm.fan_out for lm in self.layers[n:]))


def map_layer(fan_in: int, fan_out: int, rows: int = CORE_ROWS,
              cols: int = CORE_COLS) -> LayerMap:
    fan_in = fan_in + 1  # +1 bias row (Fig. 8: "One additional input ... bias")
    row_tiles = math.ceil(fan_in / rows)
    col_tiles = math.ceil(fan_out / cols)
    cores = row_tiles * col_tiles
    agg_cores = 0
    if row_tiles > 1:
        # Aggregation neurons: fan_out neurons each taking row_tiles inputs.
        agg_cores = math.ceil(row_tiles / rows) * math.ceil(fan_out / cols)
    routed = fan_out * row_tiles if row_tiles > 1 else fan_out
    return LayerMap(fan_in - 1, fan_out, row_tiles, col_tiles, cores,
                    agg_cores, routed)


def _pack_shared(layer_maps: list[LayerMap], rows: int,
                 cols: int) -> list[LayerMap]:
    """Greedy loopback packing: consecutive single-core layers share one
    core while their combined (fan_in+1) rows and fan_out columns fit the
    crossbar.  The shared core processes the resident layers in sequence
    through the routing-switch loopback (Fig. 2), so only *area* changes;
    per-layer execution cost does not."""
    packed: list[LayerMap] = []
    used_rows = used_cols = 0
    open_group = False
    for lm in layer_maps:
        single = lm.row_tiles == 1 and lm.col_tiles == 1 and lm.agg_cores == 0
        if not single:
            packed.append(lm)
            open_group = False
            continue
        need_r, need_c = lm.fan_in + 1, lm.fan_out
        if (open_group and used_rows + need_r <= rows
                and used_cols + need_c <= cols):
            packed.append(dataclasses.replace(lm, shared=True))
            used_rows += need_r
            used_cols += need_c
        else:
            packed.append(lm)
            used_rows, used_cols = need_r, need_c
            open_group = True
    return packed


def map_network(dims: list[int], rows: int = CORE_ROWS,
                cols: int = CORE_COLS, *,
                share_small_layers: bool = False) -> NetworkMap:
    layer_maps = [map_layer(i, o, rows, cols) for i, o in zip(dims, dims[1:])]
    if share_small_layers:
        layer_maps = _pack_shared(layer_maps, rows, cols)
    cores = sum(l.placed_cores for l in layer_maps)
    routed = sum(l.routed_outputs for l in layer_maps)
    return NetworkMap(tuple(layer_maps), cores, routed, routing_cycles=routed)


def map_conv_layer(shape: ConvShape, rows: int = CORE_ROWS,
                   cols: int = CORE_COLS) -> ConvLayerMap:
    """A convolution layer's core and pool (module doc).  The kernel must
    fit one core: a kernel split over cores would need its partial sums
    aggregated at every position, which neither the mapper nor the sim
    models."""
    h, w = shape.out_hw
    if h < 1 or w < 1 or h % shape.pool or w % shape.pool:
        raise ValueError(f"{shape}: the pool must tile the {h}x{w} "
                         f"output map")
    core = map_layer(shape.fan_in, shape.out, rows, cols)
    if core.cores != 1:
        raise NotImplementedError(
            f"{shape}: a {shape.fan_in}x{shape.out} kernel needs "
            f"{core.cores} cores; conv layers are placed on one core")
    P = shape.positions
    pooled = shape.pooled_size if shape.pool > 1 else 0
    return ConvLayerMap(shape, core, P, P * shape.out,
                        PoolMap(shape.pool, pooled))


def map_conv_network(shapes: list[ConvShape], dims: list[int],
                     rows: int = CORE_ROWS, cols: int = CORE_COLS
                     ) -> NetworkMap:
    """A conv network: the conv layers ``shapes`` in order, then the
    dense chain ``dims`` (``dims[0]`` is the last pooled map's size).
    Each conv layer's stage map is its core with the layer's routed
    outputs (codes and pooled values); `map_network(dims)` and its
    callers are untouched."""
    convs = tuple(map_conv_layer(s, rows, cols) for s in shapes)
    widths = [c.shape.pooled_size for c in convs]
    ins = [c.shape.in_size for c in convs[1:]] + [dims[0]]
    if widths != ins:
        raise ValueError(f"conv layers hand on {widths} lanes, the next "
                         f"layers take {ins}")
    dense = map_network(dims, rows, cols)
    layers = tuple(dataclasses.replace(c.core,
                                       routed_outputs=c.routed_outputs)
                   for c in convs) + dense.layers
    routed = sum(lm.routed_outputs for lm in layers)
    return NetworkMap(layers, sum(lm.placed_cores for lm in layers), routed,
                      routing_cycles=routed, conv=convs)


def split_network(nmap: NetworkMap, *, max_cores_per_chip: int | None = None,
                  n_chips: int | None = None) -> tuple[tuple[int, ...], ...]:
    """Partition a mapped network's layers into contiguous per-chip groups.

    The pipeline-parallel fabric (``repro.sim.fabric``, DESIGN.md §7) uses
    this when a network's placed core count exceeds one chip's budget: each
    group becomes one chip's stage slice, and layer boundaries between
    groups become inter-chip link crossings.  Two modes:

      * ``max_cores_per_chip`` — greedy first-fit: open a new chip whenever
        the next layer would overflow the budget.  A loopback-shared layer
        (``LayerMap.shared``) rides in the previous layer's physical core,
        so it can never open a new chip (its placed-core cost is 0 and the
        core it shares must be on the same chip);
      * ``n_chips`` — balanced contiguous partition into exactly
        ``n_chips`` groups, minimizing the busiest chip's placed cores
        (linear-partition dynamic program).

    Returns a tuple of per-chip layer-index tuples covering ``nmap.layers``
    in order.  Raises when a single layer exceeds the budget (a stage
    cannot be split across chips — the mapper already split it into cores)
    or when ``n_chips`` exceeds the splittable group count.
    """
    if (max_cores_per_chip is None) == (n_chips is None):
        raise ValueError(
            "pass exactly one of max_cores_per_chip= or n_chips=")
    costs = [lm.placed_cores for lm in nmap.layers]
    n = len(costs)
    if max_cores_per_chip is not None:
        budget = max_cores_per_chip
        too_big = [i for i, c in enumerate(costs) if c > budget]
        if too_big:
            raise ValueError(
                f"layer(s) {too_big} exceed {budget} cores alone; a single "
                f"stage cannot be pipeline-split across chips")
        groups: list[list[int]] = [[]]
        used = 0
        for i, c in enumerate(costs):
            # a shared layer (c == 0) always stays with its host core
            if groups[-1] and c and used + c > budget:
                groups.append([])
                used = 0
            groups[-1].append(i)
            used += c
        return tuple(tuple(g) for g in groups)
    # balanced contiguous K-way partition (classic linear-partition DP on
    # prefix sums); shared layers glue to the preceding layer first so no
    # group boundary can separate a loopback-shared layer from its host.
    blocks: list[list[int]] = []
    for i, c in enumerate(costs):
        if blocks and c == 0 and nmap.layers[i].shared:
            blocks[-1].append(i)
        else:
            blocks.append([i])
    k = n_chips
    if not 1 <= k <= len(blocks):
        raise ValueError(f"cannot split {len(blocks)} placeable stage "
                         f"groups over {k} chips")
    bcost = [sum(costs[i] for i in b) for b in blocks]
    nb = len(blocks)
    prefix = [0]
    for c in bcost:
        prefix.append(prefix[-1] + c)
    INF = float("inf")
    # best[j][i]: minimal max-group cost splitting the first i blocks into j
    best = [[INF] * (nb + 1) for _ in range(k + 1)]
    cut = [[0] * (nb + 1) for _ in range(k + 1)]
    best[0][0] = 0.0
    for j in range(1, k + 1):
        for i in range(j, nb + 1):
            for s in range(j - 1, i):
                cand = max(best[j - 1][s], prefix[i] - prefix[s])
                if cand < best[j][i]:
                    best[j][i] = cand
                    cut[j][i] = s
    bounds = [nb]
    for j in range(k, 0, -1):
        bounds.append(cut[j][bounds[-1]])
    bounds.reverse()
    return tuple(
        tuple(i for b in blocks[lo:hi] for i in b)
        for lo, hi in zip(bounds, bounds[1:]))


def map_autoencoder_pretraining(dims: list[int], rows: int = CORE_ROWS,
                                cols: int = CORE_COLS, *,
                                share_small_layers: bool = False
                                ) -> NetworkMap:
    """Layer-wise AE pretraining instantiates, per hidden layer, the encoder
    plus a temporary decoder back to the layer input (section III.D) — the
    hardware must provision cores for both, which is why the paper's core
    counts (Table III) exceed the plain feed-forward mapping."""
    layer_maps: list[LayerMap] = []
    for i, o in zip(dims, dims[1:]):
        layer_maps.append(map_layer(i, o, rows, cols))      # encoder layer
        layer_maps.append(map_layer(o, i, rows, cols))      # temp decoder
    if share_small_layers:
        layer_maps = _pack_shared(layer_maps, rows, cols)
    cores = sum(l.placed_cores for l in layer_maps)
    routed = sum(l.routed_outputs for l in layer_maps)
    return NetworkMap(tuple(layer_maps), cores, routed, routing_cycles=routed)


def map_pretrain_path(dims: list[int], layer: int, rows: int = CORE_ROWS,
                      cols: int = CORE_COLS) -> NetworkMap:
    """The path one pretraining step of hidden layer ``layer`` runs: the
    frozen encoders ``0 .. layer-1``, encoder ``layer`` and its temporary
    decoder, as `map_autoencoder_pretraining` provisions them (encoder j
    is its layer ``2j``, decoder j its layer ``2j + 1``).  The path's
    widths are ``dims[:layer + 2] + [dims[layer]]``."""
    if not 0 <= layer < len(dims) - 1:
        raise ValueError(f"no hidden layer {layer} in {len(dims) - 1} "
                         f"layers")
    pre = map_autoencoder_pretraining(dims, rows, cols).layers
    lms = pre[0:2 * layer + 1:2] + (pre[2 * layer + 1],)
    routed = sum(lm.routed_outputs for lm in lms)
    return NetworkMap(lms, sum(lm.placed_cores for lm in lms), routed,
                      routing_cycles=routed)
