"""Pallas TPU kernels for the crossbar layer (forward / backward / update).

Hardware adaptation (DESIGN.md §2): the paper's 400x200 analog crossbar tile
becomes an MXU-aligned VMEM tile.  The default logical tile is 512x128
(fan-in x neurons): the *bounded-tile* discipline survives, the exact
dimensions are re-derived for the MXU (128-multiples) and a VMEM working set
of  bm*bk + bk*bn*2 + bm*bn  fp32 words  =  128*512 + 512*128*2 + 128*128
≈ 0.9 MB — comfortably inside the ~16 MB v5e VMEM even with double
buffering.

Each kernel fuses what the paper's core fuses:
  fwd:    differential-pair subtraction + matmul + hard-sigmoid epilogue
          (+ optional in-kernel 3-bit output-ADC quantization, so chained
          layers never round-trip activations through a separate quant op)
  bwd:    transposed matmul through the same conductance pair, with 8-bit
          sign-magnitude error codes dequantized in-kernel (codes + scale in,
          fp32 out — the error never materializes at full precision in HBM)
  dw:     outer-product gradient accumulation x^T @ delta over the batch
          grid axis, with the same fused error dequantization
  update: outer-product + pulse discretization + conductance clipping
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.crossbar import F32

# Logical tile: paper's 400(+bias)x100 crossbar, MXU-aligned.
TILE_ROWS = 512     # fan-in per tile  (paper: 400)
TILE_COLS = 128     # neurons per tile (paper: 100)
TILE_M = 128        # batch tile


def _dimension_semantics(n_parallel: int, n_arbitrary: int):
    """TPU compiler parameters for a grid of ``n_parallel`` independent axes
    followed by ``n_arbitrary`` revisiting (accumulating) axes.  A failure
    to build them propagates: a kernel lowered without them would be a
    different program from the one the tests compile."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel
        + ("arbitrary",) * n_arbitrary)


def _scale_spec():
    """BlockSpec for a (1, 1) per-tensor dequantization scale, broadcast to
    every grid cell."""
    return pl.BlockSpec((1, 1), lambda i, j, k: (0, 0))


# ---------------------------------------------------------------------------
# Forward: y = ADC(h(x @ (G+ - G-)))
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, gp_ref, gm_ref, o_ref, *, n_k: int, activation: bool,
                adc_bits: int | None, adc_range: float):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = gp_ref[...].astype(jnp.float32) - gm_ref[...].astype(jnp.float32)
    o_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w, precision=F32,
                          preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        o = o_ref[...]
        if activation:
            o = jnp.clip(o * 0.25, -0.5, 0.5)
        if adc_bits is not None:
            # fused output ADC (paper section IV.A): fixed-range uniform
            # quantization over the op-amp rails — same math as
            # core.quantization.adc_quantize with a static scale.
            levels = float(2 ** adc_bits - 1)
            scale = 2.0 * adc_range / levels
            o = jnp.clip(o, -adc_range, adc_range)
            o = jnp.round((o + adc_range) / scale) * scale - adc_range
        o_ref[...] = o


def crossbar_fwd_kernel(x: jax.Array, g_plus: jax.Array, g_minus: jax.Array,
                        *, activation: bool = True,
                        adc_bits: int | None = None,
                        adc_range: float = 0.5,
                        bm: int = TILE_M, bk: int = TILE_ROWS,
                        bn: int = TILE_COLS,
                        interpret: bool = True) -> jax.Array:
    """x: (M, K); g±: (K, N) -> (M, N) fp32.

    ``adc_bits`` fuses the output-ADC quantization into the epilogue so a
    chained next layer consumes transport-quantized activations directly.
    """
    M, K = x.shape
    _, N = g_plus.shape
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (x.shape, (bm, bk, bn))
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, n_k=grid[2], activation=activation,
                          adc_bits=adc_bits, adc_range=adc_range),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=None if interpret else _dimension_semantics(2, 1),
        interpret=interpret,
    )(x, g_plus, g_minus)


# ---------------------------------------------------------------------------
# Backward: dx = dequant(dy) @ (G+ - G-)^T   (contracting the neuron axis)
# ---------------------------------------------------------------------------

def _bwd_kernel(*refs, n_k: int, dequant: bool):
    if dequant:
        dy_ref, gp_ref, gm_ref, scale_ref, o_ref = refs
    else:
        dy_ref, gp_ref, gm_ref, o_ref = refs
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    dy = dy_ref[...].astype(jnp.float32)
    if dequant:
        # paper III.F step 1: errors travel as 8-bit sign-magnitude codes;
        # the shared full-scale is applied here, inside the kernel.
        dy = dy * scale_ref[0, 0]
    w = gp_ref[...].astype(jnp.float32) - gm_ref[...].astype(jnp.float32)
    # dy (bm, bn) x w (bk, bn)^T -> (bm, bk)
    o_ref[...] += jax.lax.dot_general(
        dy, w,
        dimension_numbers=(((1,), (1,)), ((), ())), precision=F32,
        preferred_element_type=jnp.float32)


def crossbar_bwd_kernel(dy: jax.Array, g_plus: jax.Array, g_minus: jax.Array,
                        *, dy_scale: jax.Array | None = None,
                        bm: int = TILE_M, bk: int = TILE_ROWS,
                        bn: int = TILE_COLS,
                        interpret: bool = True) -> jax.Array:
    """dy: (M, N); g±: (K, N) -> dx (M, K) fp32.

    When ``dy_scale`` is given, ``dy`` holds integer sign-magnitude error
    codes (paper's 8-bit links) and is dequantized in-kernel as
    ``codes * scale``.
    """
    M, N = dy.shape
    K, _ = g_plus.shape
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0
    grid = (M // bm, K // bk, N // bn)
    dequant = dy_scale is not None
    in_specs = [
        pl.BlockSpec((bm, bn), lambda i, j, n: (i, n)),
        pl.BlockSpec((bk, bn), lambda i, j, n: (j, n)),
        pl.BlockSpec((bk, bn), lambda i, j, n: (j, n)),
    ]
    args = [dy, g_plus, g_minus]
    if dequant:
        in_specs.append(_scale_spec())
        args.append(jnp.asarray(dy_scale, jnp.float32).reshape(1, 1))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, n_k=grid[2], dequant=dequant),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bk), lambda i, j, n: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, K), jnp.float32),
        compiler_params=None if interpret else _dimension_semantics(2, 1),
        interpret=interpret,
    )(*args)


# ---------------------------------------------------------------------------
# Weight gradient: dw = x^T @ dequant(dy)   (contracting the batch axis)
# ---------------------------------------------------------------------------

def _dw_kernel(*refs, n_m: int, dequant: bool):
    if dequant:
        x_ref, dy_ref, scale_ref, o_ref = refs
    else:
        x_ref, dy_ref, o_ref = refs
    m = pl.program_id(2)

    @pl.when(m == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    dy = dy_ref[...].astype(jnp.float32)
    if dequant:
        dy = dy * scale_ref[0, 0]
    # x (bm, bk)^T x dy (bm, bn) -> (bk, bn), accumulated over the m axis
    o_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), dy,
        dimension_numbers=(((0,), (0,)), ((), ())), precision=F32,
        preferred_element_type=jnp.float32)


def crossbar_dw_kernel(x: jax.Array, dy: jax.Array, *,
                       dy_scale: jax.Array | None = None,
                       bm: int = TILE_M, bk: int = TILE_ROWS,
                       bn: int = TILE_COLS,
                       interpret: bool = True) -> jax.Array:
    """x: (M, K); dy: (M, N) -> dw (K, N) fp32 (batch-summed outer product).

    The conductance-pair gradients are ±dw: the two columns of a synapse
    move oppositely (paper III.F step 3), so one accumulation serves both.
    """
    M, K = x.shape
    _, N = dy.shape
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0
    grid = (K // bk, N // bn, M // bm)
    dequant = dy_scale is not None
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, m: (m, i)),
        pl.BlockSpec((bm, bn), lambda i, j, m: (m, j)),
    ]
    args = [x, dy]
    if dequant:
        in_specs.append(_scale_spec())
        args.append(jnp.asarray(dy_scale, jnp.float32).reshape(1, 1))
    return pl.pallas_call(
        functools.partial(_dw_kernel, n_m=grid[2], dequant=dequant),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bk, bn), lambda i, j, m: (i, j)),
        out_shape=jax.ShapeDtypeStruct((K, N), jnp.float32),
        compiler_params=None if interpret else _dimension_semantics(2, 1),
        interpret=interpret,
    )(*args)


# ---------------------------------------------------------------------------
# Fused per-stage training megakernel: fwd + bwd-error + dw + pulse update
# ---------------------------------------------------------------------------

def _train_kernel(*refs, n_i: int, lr: float,
                  max_dw: float, levels: int, w_max: float,
                  compute_y: bool, dequant: bool):
    if dequant:
        gp_ref, gm_ref, x_ref, d_ref, scale_ref, \
            y_ref, dx_ref, gpo_ref, gmo_ref = refs
    else:
        gp_ref, gm_ref, x_ref, d_ref, y_ref, dx_ref, gpo_ref, gmo_ref = refs
    i, j, l = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    # the conductance pair is read from VMEM ONCE per grid cell and feeds
    # all three contractions below — the four-call path reads it once per
    # kernel (fwd, bwd, update), three HBM round-trips for the same tile.
    w = gp_ref[...].astype(jnp.float32) - gm_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    if dequant:
        # paper III.F step 1: the error arrives as sign-magnitude codes
        # with a shared full-scale, dequantized in-VMEM exactly as in the
        # bwd/dw kernels.
        d = d * scale_ref[0, 0]

    # forward partial y(i, l) accumulated over the fan-in grid axis j —
    # identical accumulation order to crossbar_fwd_kernel's k axis.
    @pl.when(j == 0)
    def _init_y():
        y_ref[...] = jnp.zeros_like(y_ref)

    if compute_y:
        y_ref[...] += jnp.dot(x, w, precision=F32,
                              preferred_element_type=jnp.float32)

    # backward error dx(i, j) accumulated over the neuron grid axis l —
    # identical order to crossbar_bwd_kernel's n axis.
    @pl.when(l == 0)
    def _init_dx():
        dx_ref[...] = jnp.zeros_like(dx_ref)

    dx_ref[...] += jax.lax.dot_general(
        d, w, dimension_numbers=(((1,), (1,)), ((), ())), precision=F32,
        preferred_element_type=jnp.float32)

    # weight update: gpo doubles as the fp32 dw accumulator over the batch
    # grid axis i (identical order to pulse_update_kernel's m axis), with
    # the pulse discretization + clipping applied on the last batch tile.
    @pl.when(i == 0)
    def _init_dw():
        gpo_ref[...] = jnp.zeros_like(gpo_ref)

    gpo_ref[...] += 2.0 * lr * jax.lax.dot_general(
        x, d, dimension_numbers=(((0,), (0,)), ((), ())), precision=F32,
        preferred_element_type=jnp.float32)

    @pl.when(i == n_i - 1)
    def _apply():
        unit = max_dw / levels
        dw = jnp.clip(jnp.round(gpo_ref[...] / unit), -levels, levels) * unit
        gpo_ref[...] = jnp.clip(gp_ref[...].astype(jnp.float32) + 0.5 * dw,
                                0.0, w_max)
        gmo_ref[...] = jnp.clip(gm_ref[...].astype(jnp.float32) - 0.5 * dw,
                                0.0, w_max)


def crossbar_train_kernel(g_plus: jax.Array, g_minus: jax.Array,
                          x: jax.Array, delta: jax.Array, *, lr: float,
                          dy_scale: jax.Array | None = None,
                          max_dw: float = 0.05, levels: int = 128,
                          w_max: float = 1.0, compute_y: bool = False,
                          bm: int = TILE_M, bk: int = TILE_ROWS,
                          bn: int = TILE_COLS, interpret: bool = True
                          ) -> tuple[jax.Array, jax.Array, jax.Array,
                                     jax.Array]:
    """One crossbar's whole training step in ONE kernel (DESIGN.md §8).

    x: (M, K); delta: (M, N); g±: (K, N) ->
        (y (M, N), dx (M, K), g+', g-').

    Fuses what the four-call path (fwd, bwd, dw, pulse) dispatches
    separately: each grid cell loads one conductance tile and drives the
    forward partial (``compute_y``), the transposed error contraction, and
    the batch-summed outer product + pulse update from that single read.
    Per-output accumulation orders match the standalone kernels exactly, so
    at equal block sizes the results are bitwise identical to the four-call
    sequence (pinned by ``tests/test_compiled_step.py``).  ``dy_scale``
    selects the 8-bit sign-magnitude error path (codes in ``delta``,
    dequantized in-kernel).  All three reduction axes are ``arbitrary`` on
    TPU — every output window is revisited along its own grid axis.
    """
    M, K = x.shape
    _, N = delta.shape
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0
    grid = (M // bm, K // bk, N // bn)
    dequant = dy_scale is not None
    in_specs = [
        pl.BlockSpec((bk, bn), lambda i, j, l: (j, l)),
        pl.BlockSpec((bk, bn), lambda i, j, l: (j, l)),
        pl.BlockSpec((bm, bk), lambda i, j, l: (i, j)),
        pl.BlockSpec((bm, bn), lambda i, j, l: (i, l)),
    ]
    args = [g_plus, g_minus, x, delta]
    if dequant:
        in_specs.append(pl.BlockSpec((1, 1), lambda i, j, l: (0, 0)))
        args.append(jnp.asarray(dy_scale, jnp.float32).reshape(1, 1))
    out = pl.pallas_call(
        functools.partial(_train_kernel, n_i=grid[0], lr=lr,
                          max_dw=max_dw, levels=levels,
                          w_max=w_max, compute_y=compute_y, dequant=dequant),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, l: (i, l)),
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, j)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (j, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (j, l)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), jnp.float32),
            jax.ShapeDtypeStruct((M, K), jnp.float32),
            jax.ShapeDtypeStruct((K, N), jnp.float32),
            jax.ShapeDtypeStruct((K, N), jnp.float32),
        ],
        compiler_params=None if interpret else _dimension_semantics(0, 3),
        interpret=interpret,
    )(*args)
    return out[0], out[1], out[2], out[3]


# ---------------------------------------------------------------------------
# Update: G± <- clip(G± ± pulse(lr * x^T delta)/2)
# ---------------------------------------------------------------------------

def _upd_kernel(gp_ref, gm_ref, x_ref, d_ref, gp_out, gm_out, *,
                n_m: int, lr: float, max_dw: float, levels: int, w_max: float):
    # gp_out doubles as the fp32 dw accumulator until the last m step
    # (its (i, j) block is revisited across the m axis).
    m = pl.program_id(2)

    @pl.when(m == 0)
    def _init():
        gp_out[...] = jnp.zeros_like(gp_out)

    # accumulate dw tile = 2*lr * x^T @ delta over the batch dimension
    gp_out[...] += 2.0 * lr * jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), d_ref[...].astype(jnp.float32),
        dimension_numbers=(((0,), (0,)), ((), ())), precision=F32,
        preferred_element_type=jnp.float32)

    @pl.when(m == n_m - 1)
    def _apply():
        unit = max_dw / levels
        dw = jnp.clip(jnp.round(gp_out[...] / unit), -levels, levels) * unit
        gp_out[...] = jnp.clip(gp_ref[...].astype(jnp.float32) + 0.5 * dw,
                               0.0, w_max)
        gm_out[...] = jnp.clip(gm_ref[...].astype(jnp.float32) - 0.5 * dw,
                               0.0, w_max)


def pulse_update_kernel(g_plus: jax.Array, g_minus: jax.Array, x: jax.Array,
                        delta: jax.Array, *, lr: float, max_dw: float = 0.05,
                        levels: int = 128, w_max: float = 1.0,
                        bm: int = TILE_M, bk: int = TILE_ROWS,
                        bn: int = TILE_COLS, interpret: bool = True
                        ) -> tuple[jax.Array, jax.Array]:
    """x: (M, K); delta: (M, N); g±: (K, N) -> updated (g+, g-)."""
    M, K = x.shape
    _, N = delta.shape
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0
    grid = (K // bk, N // bn, M // bm)
    out = pl.pallas_call(
        functools.partial(_upd_kernel, n_m=grid[2], lr=lr, max_dw=max_dw,
                          levels=levels, w_max=w_max),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bn), lambda i, j, m: (i, j)),
            pl.BlockSpec((bk, bn), lambda i, j, m: (i, j)),
            pl.BlockSpec((bm, bk), lambda i, j, m: (m, i)),
            pl.BlockSpec((bm, bn), lambda i, j, m: (m, j)),
        ],
        out_specs=[
            pl.BlockSpec((bk, bn), lambda i, j, m: (i, j)),
            pl.BlockSpec((bk, bn), lambda i, j, m: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, N), jnp.float32),
            jax.ShapeDtypeStruct((K, N), jnp.float32),
        ],
        compiler_params=None if interpret else _dimension_semantics(2, 1),
        interpret=interpret,
    )(g_plus, g_minus, x, delta)
    return out[0], out[1]
