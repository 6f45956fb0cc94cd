"""Plain reference of a convolutional network on the crossbar cores
(LeNet-5 on the paper's chip, DESIGN.md "Conv stages").

The forward pass and one training step written out in `jax.numpy`, with
no kernels and no stage envelope: what `sim.VirtualChip` built with
``conv=`` must compute.  Every contraction runs in float32 at
`Precision.HIGHEST` (``jax.default_matmul_precision("highest")``).

Maps are flattened row-major, channels last.  For a conv layer
(`mapping.ConvShape`):

  * ``patches(h, s)`` is the (B*H'*W', k*k*C) patch matrix of the map
    ``h`` (stride 1, no padding), positions row-major, lanes (dy, dx, c);
  * ``pool(a, s, spec)`` is the router's ``pool x pool`` mean: each
    activation leaves its core as a 3-bit ADC code, the router sums a
    window's codes (a 5-bit value for 2x2, which fits section V.C's
    8-bit link) and the next core takes sum / pool**2 exactly; nothing
    is re-quantized;
  * ``col2im`` is the transpose of ``patches`` (a patch error added back
    into every lane it read), ``unpool`` the pool's error split evenly
    over the window.

Forward::

    a = h(P(x) @ W)  ->  pool(a)   for each conv layer, then the dense
    chain as `core.crossbar.paper_backprop_step` runs it (its input, the
    last pooled map, is not re-quantized).

Backward: the dense chain as in `paper_backprop_step`, then for each conv
layer from the last: ``e = unpool(error)``, ``d = E(e)`` (8-bit codes),
``local = d * h'(dp)``, ``dW = 2 lr P^T @ local / B`` (summed over rows
and positions, divided by the batch alone), pulses and clipping as for a
dense layer, and the error into the layer below ``col2im(local @ W^T)``;
no error is formed below the first layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import quantization as q
from repro.core.crossbar import (CrossbarSpec, clip_conductance,
                                 hard_sigmoid, hard_sigmoid_deriv,
                                 reconstruct)
from repro.core.mapping import ConvShape

#: LeNet-5 (LeCun et al. 1998) at its published widths: C1 6@5x5 over
#: 32x32x1, S2 2x2, C3 16@5x5 over 14x14x6 (every S2 map to every C3
#: map), S4 2x2, then C5 (a dense 400 -> 120), F6 and the 10 outputs
LENET5_CONV = (ConvShape((32, 32, 1), 5, 6, 2),
               ConvShape((14, 14, 6), 5, 16, 2))
LENET5_DIMS = (400, 120, 84, 10)


def patches(h: jax.Array, s: ConvShape) -> jax.Array:
    """(B, H*W*C) maps -> the (B*H'*W', k*k*C) patch matrix."""
    H, W, C = s.in_shape
    Ho, Wo = s.out_hw
    m = h.reshape(-1, H, W, C)
    cols = [m[:, dy:dy + Ho, dx:dx + Wo, :]
            for dy in range(s.k) for dx in range(s.k)]
    return jnp.stack(cols, axis=3).reshape(-1, s.fan_in)


def col2im(e: jax.Array, s: ConvShape) -> jax.Array:
    """(B*H'*W', k*k*C) patch errors -> (B, H*W*C): each lane's error is
    the sum, in (dy, dx) order, of the patch lanes that read it."""
    H, W, C = s.in_shape
    Ho, Wo = s.out_hw
    e = e.reshape(-1, Ho, Wo, s.k * s.k, C)
    out = jnp.zeros((e.shape[0], H, W, C), e.dtype)
    for i in range(s.k * s.k):
        dy, dx = divmod(i, s.k)
        out = out.at[:, dy:dy + Ho, dx:dx + Wo, :].add(e[:, :, :, i, :])
    return out.reshape(e.shape[0], -1)


def pool(a: jax.Array, s: ConvShape, spec: CrossbarSpec) -> jax.Array:
    """(B*H'*W', out) activations -> (B, pooled size): the window's codes
    summed in the router, divided by the window (module doc)."""
    Ho, Wo = s.out_hw
    p = s.pool
    if spec.transport_quant:
        levels = 2 ** spec.adc_bits - 1
        codes = jnp.round((q.adc_quantize(a, spec.adc_bits) + 0.5) * levels)
        sums = codes.reshape(-1, Ho // p, p, Wo // p, p, s.out).sum((2, 4))
        v = sums / (p * p * levels) - 0.5
    else:
        v = a.reshape(-1, Ho // p, p, Wo // p, p, s.out).sum((2, 4)) \
            / (p * p)
    return v.reshape(v.shape[0], -1)


def unpool(e: jax.Array, s: ConvShape) -> jax.Array:
    """(B, pooled size) errors -> (B*H'*W', out): each lane of a window
    takes the window's error over the window's size."""
    Ho, Wo = s.out_hw
    p = s.pool
    e = e.reshape(-1, Ho // p, 1, Wo // p, 1, s.out) / (p * p)
    e = jnp.broadcast_to(e, (e.shape[0], Ho // p, p, Wo // p, p, s.out))
    return e.reshape(-1, s.out)


def _w(p: dict) -> jax.Array:
    return reconstruct(p["g_plus"], p["g_minus"])


def _update(p: dict, x: jax.Array, local: jax.Array, lr: float, batch: int,
            spec: CrossbarSpec) -> dict:
    dw = 2.0 * lr * (x.T @ local) / batch
    if spec.update_quant:
        dw = q.pulse_discretize(dw, spec.max_update, spec.update_levels)
    return {"g_plus": clip_conductance(p["g_plus"] + 0.5 * dw, spec),
            "g_minus": clip_conductance(p["g_minus"] - 0.5 * dw, spec)}


def _codes(e: jax.Array, spec: CrossbarSpec) -> jax.Array:
    if spec.error_quant:
        return q.error_quantize(e, spec.err_bits).dequantize()
    return e


def forward(conv: list[dict], dense: list[dict], x: jax.Array,
            shapes, spec: CrossbarSpec) -> jax.Array:
    """Recognition: the network's output for the (B, H*W*C) inputs."""
    with jax.default_matmul_precision("highest"):
        h = x
        for p, s in zip(conv, shapes):
            h = pool(hard_sigmoid(patches(h, s) @ _w(p)), s, spec)
        for li, p in enumerate(dense):
            if spec.transport_quant and li > 0:
                h = q.adc_quantize(h, spec.adc_bits)
            h = hard_sigmoid(h @ _w(p))
    return h


def train_step(conv: list[dict], dense: list[dict], x: jax.Array,
               target: jax.Array, lr: float, shapes, spec: CrossbarSpec
               ) -> tuple[list[dict], list[dict], jax.Array]:
    """One training step on a batch: (new conv layers, new dense layers,
    output error ``target - out``)."""
    batch = x.shape[0]
    with jax.default_matmul_precision("highest"):
        pats, cdps = [], []
        h = x
        for p, s in zip(conv, shapes):
            pats.append(patches(h, s))
            cdps.append(pats[-1] @ _w(p))
            h = pool(hard_sigmoid(cdps[-1]), s, spec)
        acts, dps = [], []
        for li, p in enumerate(dense):
            if spec.transport_quant and li > 0:
                h = q.adc_quantize(h, spec.adc_bits)
            acts.append(h)
            dps.append(h @ _w(p))
            h = hard_sigmoid(dps[-1])
        err = target - h

        delta = err
        new_dense = list(dense)
        for li in reversed(range(len(dense))):
            local = _codes(delta, spec) * hard_sigmoid_deriv(dps[li])
            new_dense[li] = _update(dense[li], acts[li], local, lr, batch,
                                    spec)
            delta = local @ _w(dense[li]).T
        new_conv = list(conv)
        for li in reversed(range(len(conv))):
            s = shapes[li]
            local = _codes(unpool(delta, s), spec) \
                * hard_sigmoid_deriv(cdps[li])
            new_conv[li] = _update(conv[li], pats[li], local, lr, batch,
                                   spec)
            if li:
                delta = col2im(local @ _w(conv[li]).T, s)
    return new_conv, new_dense, err
