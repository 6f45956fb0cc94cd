"""Quantizers implementing the paper's transport discretization.

The paper's system keeps in-core arithmetic analog (full precision here) and
quantizes only what crosses a core boundary:

  * neuron outputs: 3-bit ADC over the known activation range [-0.5, 0.5]
    (section IV.A: "Neuron outputs are discretized using a three bit ADC"),
  * backpropagated errors: 8-bit sign-magnitude (section III.F step 1:
    "Errors are discretized into 8 bit representations (one sign bit and
    7 bits for magnitude)").

All quantizers are exposed both as hard functions (used on real communication
paths) and as straight-through-estimator (STE) fakes (used inside training
graphs so gradients flow).  ``stochastic=True`` rounds stochastically, which
makes the quantizer unbiased in expectation — the property the gradient
compression collective relies on (tested in tests/test_quantization.py).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

# Paper constants.
ADC_BITS = 3          # neuron-output ADC resolution
ERROR_BITS = 8        # sign + 7 magnitude bits
ACT_RANGE = 0.5       # h(x) output range is [-0.5, 0.5]


def _round(x: jax.Array, key: jax.Array | None) -> jax.Array:
    if key is None:
        return jnp.round(x)
    noise = jax.random.uniform(key, x.shape, x.dtype)
    return jnp.floor(x + noise)


# ---------------------------------------------------------------------------
# Fixed-range uniform quantizer (the 3-bit output ADC)
# ---------------------------------------------------------------------------

def adc_quantize(x: jax.Array, bits: int = ADC_BITS, rng: jax.Array | None = None,
                 rng_range: float = ACT_RANGE) -> jax.Array:
    """Uniform quantization over the fixed range [-rng_range, rng_range].

    Mirrors the hardware ADC: the range is a property of the circuit (the
    op-amp rails), not of the data, so the scale is static.
    """
    levels = 2 ** bits - 1
    scale = (2.0 * rng_range) / levels
    x = jnp.clip(x, -rng_range, rng_range)
    q = _round((x + rng_range) / scale, rng)
    return q * scale - rng_range


def adc_quantize_ste(x: jax.Array, bits: int = ADC_BITS,
                     rng_range: float = ACT_RANGE) -> jax.Array:
    """ADC with straight-through gradients (quantization-aware training)."""
    return x + jax.lax.stop_gradient(adc_quantize(x, bits, rng_range=rng_range) - x)


# ---------------------------------------------------------------------------
# Sign-magnitude dynamic-range quantizer (the 8-bit error discretizer)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QTensor:
    """Quantized tensor: integer sign-magnitude codes plus a scale.

    ``codes`` are int8/int32 in [-(2^(bits-1)-1), 2^(bits-1)-1]; ``scale`` has
    one entry per block (per-tensor when block covers everything).
    """
    codes: jax.Array
    scale: jax.Array
    bits: int

    def dequantize(self) -> jax.Array:
        return self.codes.astype(self.scale.dtype) * self.scale


def error_quantize(x: jax.Array, bits: int = ERROR_BITS,
                   key: jax.Array | None = None,
                   block_axis: int | None = None,
                   full_scale: jax.Array | None = None) -> QTensor:
    """Paper's error discretization: sign bit + (bits-1) magnitude bits.

    The hardware uses one ADC per error line with a shared full-scale; we use
    max-abs scaling per tensor (``block_axis=None``) or per row of
    ``block_axis`` (used by the gradient-compression collective, where a scale
    per parameter block keeps large and small layers independent).
    ``full_scale`` gives the per-tensor max-abs from outside, when ``x`` is
    one shard of the tensor (the farm agrees on it with `farm_max`).
    """
    maxmag = 2 ** (bits - 1) - 1
    if full_scale is not None:
        scale = full_scale / maxmag
    elif block_axis is None:
        scale = jnp.max(jnp.abs(x)) / maxmag
    else:
        scale = jnp.max(jnp.abs(x), axis=block_axis, keepdims=True) / maxmag
    scale = jnp.where(scale == 0, 1.0, scale).astype(jnp.float32)
    mag = jnp.abs(x) / scale
    q = _round(mag, key)
    q = jnp.clip(q, 0, maxmag) * jnp.sign(x)
    dtype = jnp.int8 if bits <= 8 else jnp.int32
    return QTensor(q.astype(dtype), scale, bits)


def error_quantize_ste(x: jax.Array, bits: int = ERROR_BITS) -> jax.Array:
    return x + jax.lax.stop_gradient(error_quantize(x, bits).dequantize() - x)


# ---------------------------------------------------------------------------
# Generic symmetric fake-quant (used for ablations / beyond-paper bit sweeps)
# ---------------------------------------------------------------------------

def fake_quant(x: jax.Array, bits: int, per_channel_axis: int | None = None) -> jax.Array:
    """Symmetric max-abs fake quantization with STE."""
    maxmag = 2 ** (bits - 1) - 1
    if per_channel_axis is None:
        scale = jnp.max(jnp.abs(x)) / maxmag
    else:
        axes = tuple(i for i in range(x.ndim) if i != per_channel_axis)
        scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / maxmag
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -maxmag, maxmag) * scale
    return x + jax.lax.stop_gradient(q - x)


# ---------------------------------------------------------------------------
# Pulse discretization (the paper's weight-update granularity, section III.F)
# ---------------------------------------------------------------------------

def pulse_discretize(dw: jax.Array, max_dw: float, levels: int = 128,
                     key: jax.Array | None = None) -> jax.Array:
    """Discretize a weight update into pulse counts.

    The training circuit modulates pulse *duration* by eta*delta*f'(DP) and
    *amplitude* by the input x; the achievable conductance change is a
    discrete number of unit pulses.  ``levels`` unit pulses span ``max_dw``.
    """
    unit = max_dw / levels
    q = _round(dw / unit, key)
    q = jnp.clip(q, -levels, levels)
    return q * unit
