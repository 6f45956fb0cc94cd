"""Plain reference of a convolutional network on the crossbar cores
(LeNet-5, LeCun et al. 1998, section II.B, on the paper's chip), built
on `bench/reference.py` alone: it imports nothing of the program.

The configuration's ``conv`` lists the conv layers, each a ``k x k``
kernel of ``out`` maps over an ``in`` (H, W, C) map, stride 1, no
padding, then a ``pool x pool`` mean.  Maps are flattened row-major,
channels last; ``P(h)`` is the (B*H'*W', k*k*C) patch matrix, lanes
ordered (dy, dx, c).

  * forward: ``a = h(P(h) @ W)``; each activation leaves its core as a
    3-bit ADC code, the router sums a window's codes and the next core
    takes the sum over the window exactly (nothing is re-quantized);
    after the last pool the dense chain runs as `reference.train_step`
    runs it;
  * backward: the dense chain as `reference.train_step`; the error
    entering a pooled map is split evenly over each window's lanes,
    ``d = E(that)`` (8-bit codes), ``local = d * h'(dp)``, ``dW = 2 lr
    P^T @ local / B`` (summed over rows and positions, divided by the
    batch alone), pulses and clipping as for a dense layer; the error
    into the map below is col2im of ``local @ W^T``; none below the
    first layer.

The kernels are drawn from the seed on `CONV_STREAM`.  ``fault`` names a
planted defect, for the controls of ``bench/kinds/conv_train.py``:
``position0`` takes each conv update from the first position only,
``shifted`` reads every patch one lane along the map, ``no_quarter``
hands the pool's error back without dividing by the window.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench import reference as ref

#: the stream of the seed the conv kernels are drawn from (the dense
#: layers take stream 0, the inputs 1, the targets 2, the autoencoder
#: decoders 3)
CONV_STREAM = 4


def geometry(cfg: dict) -> tuple:
    """The conv layers as hashable ((H, W, C), k, out, pool) tuples."""
    return tuple((tuple(c["in"]), int(c["k"]), int(c["out"]),
                  int(c.get("pool", 1))) for c in cfg["conv"])


def _out_hw(g):
    (H, W, _), k = g[0], g[1]
    return H - k + 1, W - k + 1


def patches(h, g, fault=None):
    """(B, H*W*C) maps -> (B*H'*W', k*k*C); ``shifted`` reads each patch
    lane one lane further along the flattened map."""
    (H, W, C), k, _, _ = g
    Ho, Wo = _out_hw(g)
    if fault == "shifted":
        h = jnp.concatenate([h[:, 1:], jnp.zeros_like(h[:, :1])], axis=1)
    m = h.reshape(-1, H, W, C)
    cols = [m[:, dy:dy + Ho, dx:dx + Wo, :]
            for dy in range(k) for dx in range(k)]
    return jnp.stack(cols, axis=3).reshape(-1, k * k * C)


def col2im(e, g):
    """The transpose of `patches`: (B*H'*W', k*k*C) -> (B, H*W*C)."""
    (H, W, C), k, _, _ = g
    Ho, Wo = _out_hw(g)
    e = e.reshape(-1, Ho, Wo, k * k, C)
    out = jnp.zeros((e.shape[0], H, W, C), e.dtype)
    for i in range(k * k):
        dy, dx = divmod(i, k)
        out = out.at[:, dy:dy + Ho, dx:dx + Wo, :].add(e[:, :, :, i, :])
    return out.reshape(e.shape[0], -1)


def pool(a, g, spec: dict):
    """(B*H'*W', out) activations -> (B, pooled size): codes summed over
    each window, the sum over the window's size."""
    _, _, out, p = g
    Ho, Wo = _out_hw(g)
    win = (-1, Ho // p, p, Wo // p, p, out)
    if spec["transport_quant"]:
        levels = 2 ** spec["adc_bits"] - 1
        codes = jnp.round((ref.adc(a, spec["adc_bits"]) + 0.5) * levels)
        v = codes.reshape(win).sum((2, 4)) / (p * p * levels) - 0.5
    else:
        v = a.reshape(win).sum((2, 4)) / (p * p)
    return v.reshape(v.shape[0], -1)


def unpool(e, g, fault=None):
    """(B, pooled size) errors -> (B*H'*W', out), each lane of a window
    taking the window's error over the window's size (``no_quarter``:
    the whole error)."""
    _, _, out, p = g
    Ho, Wo = _out_hw(g)
    e = e.reshape(-1, Ho // p, 1, Wo // p, 1, out)
    if fault != "no_quarter":
        e = e / (p * p)
    e = jnp.broadcast_to(e, (e.shape[0], Ho // p, p, Wo // p, p, out))
    return e.reshape(-1, out)


@partial(jax.jit, static_argnames=("geom", "spec_key", "precision"))
def _forward(layers, x, geom, spec_key, precision):
    spec = dict(spec_key)
    n = len(geom)
    h = x
    for (gp, gm), g in zip(layers[:n], geom):
        h = pool(ref.hard_sigmoid(ref.matmul(patches(h, g), gp - gm,
                                             precision)), g, spec)
    for li, (gp, gm) in enumerate(layers[n:]):
        if spec["transport_quant"] and li > 0:
            h = ref.adc(h, spec["adc_bits"])
        h = ref.hard_sigmoid(ref.matmul(h, gp - gm, precision))
    return h


def forward(geom, layers, x, spec: ref.Spec, precision: str = "highest"):
    """Recognition of the (B, H*W*C) inputs ``x``; ``layers`` are the
    conv kernels' and then the dense layers' (g_plus, g_minus) pairs."""
    return _forward(tuple(layers), x, geom, spec.key(), precision)


@partial(jax.jit, static_argnames=("geom", "spec_key", "precision",
                                   "fault"))
def _train_step(layers, x, target, lr, geom, spec_key, precision, fault):
    spec = dict(spec_key)
    n = len(geom)
    batch = x.shape[0]
    pats, cdps = [], []
    h = x
    for (gp, gm), g in zip(layers[:n], geom):
        pats.append(patches(h, g, fault))
        cdps.append(ref.matmul(pats[-1], gp - gm, precision))
        h = pool(ref.hard_sigmoid(cdps[-1]), g, spec)
    acts, dps = [], []
    for li, (gp, gm) in enumerate(layers[n:]):
        if spec["transport_quant"] and li > 0:
            h = ref.adc(h, spec["adc_bits"])
        acts.append(h)
        dps.append(ref.matmul(h, gp - gm, precision))
        h = ref.hard_sigmoid(dps[-1])
    err = target - h

    new = list(layers)
    delta = err
    for li in reversed(range(len(layers) - n)):
        gp, gm = layers[n + li]
        if spec["error_quant"]:
            delta = ref.error_codes(delta, spec["err_bits"])
        local = delta * ref.hard_sigmoid_deriv(dps[li])
        new[n + li] = _update(gp, gm, acts[li], local, lr, batch, spec,
                              precision)
        delta = ref.matmul(local, (gp - gm).T, precision)
    for li in reversed(range(n)):
        gp, gm = layers[li]
        g = geom[li]
        e = unpool(delta, g, fault)
        if spec["error_quant"]:
            e = ref.error_codes(e, spec["err_bits"])
        local = e * ref.hard_sigmoid_deriv(cdps[li])
        x_upd, l_upd = pats[li], local
        if fault == "position0":
            P = _out_hw(g)[0] * _out_hw(g)[1]
            x_upd, l_upd = pats[li][::P], local[::P]
        new[li] = _update(gp, gm, x_upd, l_upd, lr, batch, spec, precision)
        if li:
            delta = col2im(ref.matmul(local, (gp - gm).T, precision), g)
    return tuple(new), err


def _update(gp, gm, x, local, lr, batch, spec, precision):
    """A layer's pulse update from its inputs ``x`` and local errors."""
    dw = 2.0 * lr * ref.matmul(x.T, local, precision) / batch
    if spec["update_quant"]:
        dw = ref.pulses(dw, spec["max_update"], spec["update_levels"])
    return (jnp.clip(gp + 0.5 * dw, 0.0, spec["w_max"]),
            jnp.clip(gm - 0.5 * dw, 0.0, spec["w_max"]))


def train_step(geom, layers, x, target, lr: float, spec: ref.Spec,
               precision: str = "highest", fault: str | None = None):
    """One training step on a batch: (new layers, output error ``target
    - out``), ``layers`` as in `forward`."""
    return _train_step(tuple(layers), x, target, jnp.float32(lr), geom,
                       spec.key(), precision, fault)


@partial(jax.jit, static_argnames=("geom", "w_max", "g_init"))
def _initial_conv_layers(words, geom, w_max, g_init):
    key = ref.seed_key(words, CONV_STREAM)
    out = []
    for i, ((_, _, C), k, o, _) in enumerate(geom):
        kp, km = jax.random.split(jax.random.fold_in(key, i))
        hi = g_init * w_max
        shape = (k * k * C, o)
        out.append((jax.random.uniform(kp, shape, jnp.float32, 0.0, hi),
                    jax.random.uniform(km, shape, jnp.float32, 0.0, hi)))
    return tuple(out)


def initial_conv_layers(cfg: dict, seed: int):
    """Each conv layer's ``(k*k*C, out)`` kernel pair, both devices
    uniform in [0, g_init * w_max), drawn on the device from ``seed`` on
    `CONV_STREAM`."""
    return _initial_conv_layers(ref.split_seed(seed), geometry(cfg),
                                float(cfg["w_max"]), float(cfg["g_init"]))
