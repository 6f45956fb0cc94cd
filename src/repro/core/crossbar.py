"""Memristor-crossbar layer: the paper's core contribution as a JAX module.

A ``CrossbarLinear`` models one (possibly tiled) layer of the paper's neural
core:

  * weights are differential conductance pairs ``w = g_plus - g_minus`` with
    conductances bounded in ``[g_min, g_max]`` (section III.B, two memristors
    per synapse),
  * the activation is the op-amp hard-sigmoid ``h(x) = clip(x/4, -0.5, 0.5)``
    (Eq. 3 / Fig. 6),
  * inputs arriving over the routing network are 3-bit ADC codes (section
    IV.A) — modeled as fixed-range fake-quant with STE,
  * backpropagated errors are 8-bit sign-magnitude (section III.F step 1) and
    travel through the *same* weights (Eq. 7 / Fig. 9) — modeled with a
    ``custom_vjp`` whose backward quantizes the incoming error before the
    transpose product,
  * layers larger than a core (400 inputs x 100 neurons) are split across
    tiles; fan-in splits follow Fig. 14 (sub-neurons plus an aggregation
    stage).

Exact-aggregation tiling (``split_activation=False``) is mathematically equal
to the unsplit matmul (property-tested); paper-faithful mode
(``split_activation=True``) puts the activation on each sub-neuron as the
hardware does, which changes the function and requires training with the
split topology — precisely the paper's note that "the network needs to be
trained based on the new network topology".
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quantization as q

# Paper constants (section IV.A, III.A).
CORE_ROWS = 400      # max fan-in per neural core
CORE_COLS = 100      # max neurons per core (crossbar is 400x200 differential)
G_ON = 1e-4          # 1/R_on,  R_on  = 10 kOhm
G_OFF = 1e-7         # 1/R_off, R_off = 10 MOhm (ratio 1000)

# Precision of every crossbar contraction: the reference math here, the
# simulator's compiled bodies and the Pallas kernels.  The numerics are
# defined in f32; a TPU's default f32 matmul takes one bf16 pass, whose
# rounding moves dot products enough to flip 3-bit ADC outputs and pulse
# counts between two implementations that should agree.
F32 = jax.lax.Precision.HIGHEST


def hard_sigmoid(x: jax.Array) -> jax.Array:
    """h(x) = x/4 clipped to [-0.5, 0.5]  (paper Eq. 3, Fig. 6)."""
    return jnp.clip(x * 0.25, -0.5, 0.5)


def hard_sigmoid_deriv(x: jax.Array) -> jax.Array:
    return jnp.where(jnp.abs(x) < 2.0, 0.25, 0.0)


@dataclasses.dataclass(frozen=True)
class CrossbarSpec:
    rows: int = CORE_ROWS            # fan-in capacity of one tile
    cols: int = CORE_COLS            # neuron capacity of one tile
    w_max: float = 1.0               # |w| representable by the conductance pair
    adc_bits: int = q.ADC_BITS       # transport quantization of activations
    err_bits: int = q.ERROR_BITS     # transport quantization of errors
    update_levels: int = 128         # pulse levels per max update (III.F)
    max_update: float = 0.05         # largest single-step |dw| (pulse budget)
    transport_quant: bool = True     # quantize inter-core activations
    error_quant: bool = True         # quantize backpropagated errors
    update_quant: bool = True        # discretize weight updates into pulses
    split_activation: bool = False   # Fig. 14 sub-neuron activation mode

    def tiles(self, fan_in: int, fan_out: int) -> tuple[int, int]:
        return (math.ceil(fan_in / self.rows), math.ceil(fan_out / self.cols))


# ---------------------------------------------------------------------------
# Conductance <-> weight mapping
# ---------------------------------------------------------------------------

def decompose(w: jax.Array, spec: CrossbarSpec) -> tuple[jax.Array, jax.Array]:
    """w -> (g_plus, g_minus) conductance pair, in weight units.

    We keep conductances in *weight units* scaled so that g in [0, w_max];
    w = g_plus - g_minus; the common mode is centered (both sides share
    |w|/2 offset from midpoint), matching the update rule that moves the two
    columns by +dw/2 and -dw/2 (section III.F step 3).
    """
    w = jnp.clip(w, -spec.w_max, spec.w_max)
    mid = 0.5 * spec.w_max
    return mid + 0.5 * w, mid - 0.5 * w


def reconstruct(g_plus: jax.Array, g_minus: jax.Array) -> jax.Array:
    return g_plus - g_minus


def clip_conductance(g: jax.Array, spec: CrossbarSpec) -> jax.Array:
    return jnp.clip(g, 0.0, spec.w_max)


def init_conductances(key: jax.Array, fan_in: int, fan_out: int,
                      spec: CrossbarSpec) -> dict[str, jax.Array]:
    """Paper step 1: "Initialize the memristors with high random resistances"
    — i.e. small random conductances, hence small random weights."""
    kp, km = jax.random.split(key)
    lo, hi = 0.0, 0.02 * spec.w_max
    gp = jax.random.uniform(kp, (fan_in, fan_out), minval=lo, maxval=hi)
    gm = jax.random.uniform(km, (fan_in, fan_out), minval=lo, maxval=hi)
    return {"g_plus": gp, "g_minus": gm}


# ---------------------------------------------------------------------------
# Forward/backward with transport quantization (custom VJP)
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _xbar_matmul(x: jax.Array, g_plus: jax.Array, g_minus: jax.Array,
                 spec: CrossbarSpec) -> jax.Array:
    w = reconstruct(g_plus, g_minus)
    return jnp.matmul(x, w, precision=F32)


def _xbar_fwd(x, g_plus, g_minus, spec):
    w = reconstruct(g_plus, g_minus)
    return jnp.matmul(x, w, precision=F32), (x, w)


def _xbar_bwd(spec, res, dy):
    x, w = res
    if spec.error_quant:
        # Paper III.F step 1: errors discretized to 8 bits before being
        # driven back through the crossbar columns (Fig. 9).
        dy = q.error_quantize(dy, spec.err_bits).dequantize()
    dx = jnp.matmul(dy, w.T, precision=F32)  # Eq. 7: delta_prev = W^T delta
    # Eq. 6 outer product (batch-summed)
    dw = jnp.einsum("...i,...j->ij", x, dy, precision=F32)
    # d/dg_plus = +dw, d/dg_minus = -dw: the two columns move oppositely,
    # matching the +dw/2 / -dw/2 hardware update convention.
    return dx, dw, -dw


_xbar_matmul.defvjp(_xbar_fwd, _xbar_bwd)


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------

def crossbar_apply(params: dict[str, jax.Array], x: jax.Array,
                   spec: CrossbarSpec, *, activation: bool = True,
                   use_kernel: bool = False,
                   transport_in: bool = True) -> jax.Array:
    """Apply one crossbar layer: y = h( (ADC(x)) @ (g+ - g-) ).

    ``x``: (..., fan_in).  Tiling over fan-in/fan-out is implicit: the matmul
    below *is* the tiled computation under exact aggregation, because tile
    partial sums add linearly (Fig. 14 with a linear aggregation stage).  The
    Pallas kernel path (kernels/crossbar.py) materializes the tiles
    explicitly with the same semantics; ``tests/test_kernels.py`` checks the
    two agree.  ``split_activation=True`` applies h() per fan-in tile first.

    ``transport_in=False`` marks an input that did NOT ride the routing
    network — the network's own input, driven through the DAC as an analog
    voltage (section IV.A quantizes *neuron outputs*, not network inputs).

    ``use_kernel=True`` routes through the differentiable fused Pallas path
    (kernels/ops.crossbar_matmul): forward, error backprop (with in-kernel
    8-bit dequant) and the weight outer product all run as kernels under
    ``jax.grad``.
    """
    gp, gm = params["g_plus"], params["g_minus"]
    fan_in = gp.shape[0]
    if spec.transport_quant and transport_in:
        x = q.adc_quantize_ste(x, spec.adc_bits)
    # Fig.-14 sub-neuron mode changes the network function per fan-in tile;
    # the fused kernel implements exact aggregation only, so split stacks
    # fall through to the reference path rather than silently computing a
    # different model.
    if use_kernel and not (spec.split_activation and fan_in > spec.rows):
        from repro.kernels import ops as kernel_ops
        dp = kernel_ops.crossbar_matmul(x, gp, gm,
                                        error_quant=spec.error_quant,
                                        err_bits=spec.err_bits)
        return hard_sigmoid(dp) if activation else dp

    if spec.split_activation and fan_in > spec.rows:
        n_tiles = math.ceil(fan_in / spec.rows)
        pad = n_tiles * spec.rows - fan_in
        xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
        gpp = jnp.pad(gp, [(0, pad), (0, 0)])
        gmp = jnp.pad(gm, [(0, pad), (0, 0)])
        xt = xp.reshape(x.shape[:-1] + (n_tiles, spec.rows))
        gpt = gpp.reshape(n_tiles, spec.rows, gp.shape[1])
        gmt = gmp.reshape(n_tiles, spec.rows, gm.shape[1])
        # sub-neuron DPs -> per-tile activation -> aggregation neuron
        sub = jnp.einsum("...tr,trn->...tn", xt, gpt - gmt, precision=F32)
        sub = hard_sigmoid(sub)
        if spec.transport_quant:  # sub-neuron outputs also ride the network
            sub = q.adc_quantize_ste(sub, spec.adc_bits)
        dp = sub.sum(axis=-2) * 4.0  # aggregation neuron with unit weights
    else:
        dp = _xbar_matmul(x, gp, gm, spec)
    return hard_sigmoid(dp) if activation else dp


def crossbar_dp(params: dict[str, jax.Array], x: jax.Array,
                spec: CrossbarSpec) -> jax.Array:
    """Dot-product (pre-activation) readout — the DP_j the training unit
    re-measures for f'(DP_j) (section III.F step 3)."""
    return crossbar_apply(params, x, spec, activation=False)


# ---------------------------------------------------------------------------
# The paper's manual training rule (pulse-based update, section III.E/III.F)
# ---------------------------------------------------------------------------

def paper_backprop_step(layers: list[dict[str, jax.Array]], x: jax.Array,
                        target: jax.Array, spec: CrossbarSpec, lr: float,
                        key: jax.Array | None = None
                        ) -> tuple[list[dict[str, jax.Array]], jax.Array]:
    """One stochastic-BP step exactly as the hardware executes it.

    This is the literal Eq. 4-6 loop with transport/error/update
    quantization, used by the paper-application examples and the Fig. 21
    reproduction.  (LM-scale training uses the autodiff path above instead.)
    Returns (updated_layers, output_error).
    """
    # -- forward, recording per-layer inputs and DPs (III.F step 1).
    # Layer 0's input is the network input: it arrives through the DAC as
    # an analog voltage, so only *inter-core* activations see the 3-bit
    # output ADC (section IV.A quantizes neuron outputs, not inputs).
    acts = [x]
    dps = []
    h = x
    for li, p in enumerate(layers):
        if spec.transport_quant and li > 0:
            h = q.adc_quantize_ste(h, spec.adc_bits)
            acts[-1] = h
        dp = jnp.matmul(h, reconstruct(p["g_plus"], p["g_minus"]),
                        precision=F32)
        dps.append(dp)
        h = hard_sigmoid(dp)
        acts.append(h)

    # -- output error (Eq. 4)
    delta = target - acts[-1]

    new_layers = [dict(p) for p in layers]
    for li in reversed(range(len(layers))):
        p = layers[li]
        w = reconstruct(p["g_plus"], p["g_minus"])
        if spec.error_quant:
            delta = q.error_quantize(delta, spec.err_bits).dequantize()
        local = delta * hard_sigmoid_deriv(dps[li])      # delta_j * f'(DP_j)
        dw = 2.0 * lr * jnp.einsum("...i,...j->ij", acts[li], local,
                                   precision=F32)
        if acts[li].ndim > 1:   # batched: average the per-sample updates
            dw = dw / np.prod(acts[li].shape[:-1])
        if spec.update_quant:
            dw = q.pulse_discretize(dw, spec.max_update, spec.update_levels, key)
        new_layers[li] = {
            "g_plus": clip_conductance(p["g_plus"] + 0.5 * dw, spec),
            "g_minus": clip_conductance(p["g_minus"] - 0.5 * dw, spec),
        }
        # back-propagate through this layer's weights (Eq. 5 / Fig. 9)
        delta = jnp.matmul(delta * hard_sigmoid_deriv(dps[li]), w.T,
                           precision=F32)
    return new_layers, target - acts[-1]


def mlp_forward(layers: list[dict[str, jax.Array]], x: jax.Array,
                spec: CrossbarSpec, *, use_kernel: bool = False) -> jax.Array:
    """Stacked crossbar forward.  The network input skips the transport ADC
    (DAC-driven, see crossbar_apply); inter-layer links are quantized.

    ``use_kernel=True`` runs the fully-fused inference path: each layer is
    one Pallas call with the hard-sigmoid *and* the output ADC in the
    kernel epilogue, so inter-layer activations never round-trip through a
    separate quantize op (DESIGN.md §2.3).
    """
    split = spec.split_activation and any(
        p["g_plus"].shape[0] > spec.rows for p in layers)
    if use_kernel and not split:   # sub-neuron stacks: reference path only
        from repro.kernels import ops as kernel_ops
        h = x
        last = len(layers) - 1
        for li, p in enumerate(layers):
            bits = (spec.adc_bits
                    if spec.transport_quant and li < last else None)
            h = kernel_ops.crossbar_fwd(h, p["g_plus"], p["g_minus"],
                                        activation=True, adc_bits=bits)
        return h
    h = x
    for li, p in enumerate(layers):
        h = crossbar_apply(p, h, spec, transport_in=(li > 0))
    return h


# ---------------------------------------------------------------------------
# Fused scan pipeline over stacked equal-shaped layers (the jitted hot loop)
# ---------------------------------------------------------------------------

def stack_layers(layers: list[dict[str, jax.Array]]) -> dict[str, jax.Array]:
    """Stack equal-shaped layer dicts into (L, fan_in, fan_out) buffers for
    the scan pipeline.  Raises if shapes are ragged (use the per-layer
    ``paper_backprop_step`` for ragged stacks)."""
    shapes = {tuple(p["g_plus"].shape) for p in layers}
    if len(shapes) != 1:
        raise ValueError(f"scan pipeline needs equal-shaped layers, got "
                         f"{sorted(shapes)}")
    return {"g_plus": jnp.stack([p["g_plus"] for p in layers]),
            "g_minus": jnp.stack([p["g_minus"] for p in layers])}


def unstack_layers(stacked: dict[str, jax.Array]) -> list[dict[str, jax.Array]]:
    L = stacked["g_plus"].shape[0]
    return [{"g_plus": stacked["g_plus"][i], "g_minus": stacked["g_minus"][i]}
            for i in range(L)]


@partial(jax.jit, static_argnames=("spec", "lr", "use_kernel"),
         donate_argnums=(0,))
def paper_backprop_step_scan(stacked: dict[str, jax.Array], x: jax.Array,
                             target: jax.Array, spec: CrossbarSpec,
                             lr: float, use_kernel: bool = True
                             ) -> tuple[dict[str, jax.Array], jax.Array]:
    """One stochastic-BP step as a jitted ``lax.scan`` pipeline.

    Same semantics as :func:`paper_backprop_step` restricted to stacked
    equal-shaped layers with deterministic pulse rounding, but the whole
    step is one compiled graph: forward scan (recording per-layer inputs
    and DPs), then a reversed scan whose body runs the Pallas bwd kernel
    (error transpose-product) and the fused pulse-update kernel per layer.
    The conductance buffers are donated, so steady-state training updates
    G± in place instead of copying per-layer dicts every step.
    """
    from repro.kernels import ops as kernel_ops

    batch = int(np.prod(x.shape[:-1])) if x.ndim > 1 else 1
    lr_eff = lr / batch

    def matmul(h, p):
        if use_kernel:
            return kernel_ops.crossbar_fwd(h, p["g_plus"], p["g_minus"],
                                           activation=False)
        return jnp.matmul(h, reconstruct(p["g_plus"], p["g_minus"]),
                          precision=F32)

    def fwd_body(h, p):
        dp = matmul(h, p)
        out = hard_sigmoid(dp)
        # transport-quantize at the core boundary; the *last* layer's output
        # is consumed by the training unit, not the network, so the raw h
        # is also emitted per layer.
        carry = (q.adc_quantize_ste(out, spec.adc_bits)
                 if spec.transport_quant else out)
        return carry, (h, dp, out)

    _, (acts, dps, outs) = jax.lax.scan(fwd_body, x, stacked)
    out = outs[-1]
    delta = target - out

    def bwd_body(delta, xs):
        p, a, dp = xs
        if spec.error_quant:
            delta = q.error_quantize(delta, spec.err_bits).dequantize()
        local = delta * hard_sigmoid_deriv(dp)
        if use_kernel:
            if spec.update_quant:
                gp, gm = kernel_ops.pulse_update(
                    p["g_plus"], p["g_minus"], a, local, lr=lr_eff,
                    max_dw=spec.max_update, levels=spec.update_levels,
                    w_max=spec.w_max)
            else:
                # continuous (non-pulsed) update, outer product on-kernel
                dw = 2.0 * lr_eff * kernel_ops.crossbar_dw(a, local)
                gp = clip_conductance(p["g_plus"] + 0.5 * dw, spec)
                gm = clip_conductance(p["g_minus"] - 0.5 * dw, spec)
            delta_prev = kernel_ops.crossbar_bwd(local, p["g_plus"],
                                                 p["g_minus"])
        else:
            dw = 2.0 * lr_eff * jnp.einsum("...i,...j->ij", a, local,
                                           precision=F32)
            if spec.update_quant:
                dw = q.pulse_discretize(dw, spec.max_update,
                                        spec.update_levels, None)
            gp = clip_conductance(p["g_plus"] + 0.5 * dw, spec)
            gm = clip_conductance(p["g_minus"] - 0.5 * dw, spec)
            delta_prev = jnp.matmul(
                local, reconstruct(p["g_plus"], p["g_minus"]).T,
                precision=F32)
        return delta_prev, {"g_plus": gp, "g_minus": gm}

    _, new_stacked = jax.lax.scan(bwd_body, delta, (stacked, acts, dps),
                                  reverse=True)
    return new_stacked, target - out
