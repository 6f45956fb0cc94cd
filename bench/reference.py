"""Plain reference of the crossbar network the cells run.

The paper's constrained multilayer perceptron (arXiv:1603.07400, sections
III.E-III.F and IV.A), written out in `jax.numpy` from the configuration
file alone.  It imports nothing of the program under test and takes none of
its state: the benchmark hands both sides the same conductances, which it
draws itself from the seed (`initial_layers`).

  * a layer is a differential conductance pair, ``w = g_plus - g_minus``;
  * the activation is ``h(x) = clip(x / 4, -0.5, 0.5)``;
  * activations that cross a core boundary are 3-bit ADC codes over
    [-0.5, 0.5]; the network input is DAC-driven and is not quantized;
  * back-propagated errors are 8-bit sign-magnitude codes with a per-tensor
    full scale;
  * an update ``2 lr mean(x delta f'(dp))`` is discretized into pulses of
    ``max_update / update_levels`` and clipped to ``[0, w_max]``.

``precision="highest"`` is the configuration's own: every contraction in
float32 at `Precision.HIGHEST`.  ``precision="bf16_3x"`` is the control, one
step below: `Precision.HIGH`, three bfloat16 passes (each float32 operand
split into a high and a low bfloat16 part, the product
``hi*hi + hi*lo + lo*hi``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: how near a level boundary an activation may round either way: some ten
#: to a hundred times float32's rounding of the sums the cells make
NEAR_EDGE = 1e-5
#: the most such activations of one row followed at one layer (2**6
#: candidates); isolet_class has about 0.13 a row at its first boundary
#: and at most 2 in any row of 512
MAX_NEAR = 6


class Spec:
    """The crossbar constraints a configuration file states."""

    def __init__(self, cfg: dict):
        self.adc_bits = int(cfg["adc_bits"])
        self.err_bits = int(cfg["err_bits"])
        self.update_levels = int(cfg["update_levels"])
        self.max_update = float(cfg["max_update"])
        self.w_max = float(cfg["w_max"])
        self.transport_quant = bool(cfg["transport_quant"])
        self.error_quant = bool(cfg["error_quant"])
        self.update_quant = bool(cfg["update_quant"])

    def key(self) -> tuple:
        return tuple(sorted(vars(self).items()))


def _split_bf16(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def matmul(a, b, precision: str):
    """``a @ b`` at the named precision.  ``bf16_3x`` is XLA's own
    `Precision.HIGH` on a TPU; the CPU computes every float32 product in
    float32 whatever the precision asks, so there the three passes are
    written out."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=HIGHEST)
    if jax.default_backend() == "tpu":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)
    a_hi, a_lo = _split_bf16(a)
    b_hi, b_lo = _split_bf16(b)
    mm = partial(jnp.matmul, precision=HIGHEST)   # exact bf16 products
    return mm(a_hi, b_hi) + mm(a_hi, b_lo) + mm(a_lo, b_hi)


def hard_sigmoid(x):
    return jnp.clip(x * 0.25, -0.5, 0.5)


def hard_sigmoid_deriv(x):
    return jnp.where(jnp.abs(x) < 2.0, 0.25, 0.0)


def adc(x, bits: int):
    """Fixed-range uniform ADC over [-0.5, 0.5], round half to even."""
    levels = 2 ** bits - 1
    scale = 1.0 / levels
    x = jnp.clip(x, -0.5, 0.5)
    return jnp.round((x + 0.5) / scale) * scale - 0.5


def error_codes(x, bits: int):
    """Sign-magnitude codes with a per-tensor max-abs full scale, decoded."""
    maxmag = 2 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(x)) / maxmag
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(jnp.abs(x) / scale), 0, maxmag) * jnp.sign(x)
    return q * scale


def pulses(dw, max_dw: float, levels: int):
    unit = max_dw / levels
    return jnp.clip(jnp.round(dw / unit), -levels, levels) * unit


@partial(jax.jit, static_argnames=("spec_key", "precision"))
def _forward(layers, x, spec_key, precision):
    spec = dict(spec_key)
    h = x
    for li, (gp, gm) in enumerate(layers):
        if spec["transport_quant"] and li > 0:
            h = adc(h, spec["adc_bits"])
        h = hard_sigmoid(matmul(h, gp - gm, precision))
    return h


def forward(layers, x, spec: Spec, precision: str = "highest"):
    """Recognition: the network's output for the rows of ``x``."""
    return _forward(tuple(layers), x, spec.key(), precision)


@partial(jax.jit, static_argnames=("precision",))
def _layer(h, gp, gm, precision):
    return hard_sigmoid(matmul(h, gp - gm, precision))


def _bucket(n: int) -> int:
    """Rows padded to a multiple of 256, so that few shapes compile."""
    return -(-n // 256) * 256


def _adc_alternatives(h, owner, bits: int):
    """The 3-bit codes of the rows of ``h`` and, for every activation
    within `NEAR_EDGE` of a level boundary, the code on its other side:
    each row becomes one candidate for every subset of its (at most
    `MAX_NEAR` nearest) such activations.  Returns (codes, owners)."""
    scale = 1.0 / (2 ** bits - 1)
    codes = np.asarray(adc(h, bits))
    hc = np.clip(np.asarray(h), -0.5, 0.5)
    to_edge = 0.5 * scale - np.abs(hc - codes)
    near = (to_edge < NEAR_EDGE) & (np.abs(hc) < 0.5)
    alt = codes + np.where(hc > codes, scale, -scale).astype(np.float32)
    out_codes, out_owner = [codes], [owner]
    for r in np.nonzero(near.any(axis=1))[0]:
        cols = np.nonzero(near[r])[0]
        cols = cols[np.argsort(to_edge[r, cols])][:MAX_NEAR]
        for mask in range(1, 2 ** len(cols)):
            row = codes[r].copy()
            pick = [c for i, c in enumerate(cols) if mask >> i & 1]
            row[pick] = alt[r, pick]
            out_codes.append(row[None])
            out_owner.append(owner[r:r + 1])
    return np.concatenate(out_codes), np.concatenate(out_owner)


def forward_candidates(layers, x, spec: Spec):
    """Recognition that allows for rounding where an activation lies at a
    3-bit level boundary.

    A float32 sum is exact only to its rounding, so an activation within
    `NEAR_EDGE` of the boundary between two ADC codes may take either code
    in a sound program, and the flip moves the answer downstream by as
    much as an error would.  Each row of ``x`` is followed through every
    such choice, layer by layer (a flip upstream may bring another
    activation to a boundary downstream).  Returns the outputs of every
    candidate (host float64) and, for each, the index of its row."""
    h = jnp.asarray(x)
    owner = np.arange(h.shape[0])
    n = h.shape[0]
    for li, (gp, gm) in enumerate(layers):
        if spec.transport_quant and li > 0:
            codes, owner = _adc_alternatives(np.asarray(h[:n]), owner,
                                             spec.adc_bits)
            n = codes.shape[0]
            h = np.zeros((_bucket(n), codes.shape[1]), np.float32)
            h[:n] = codes
            h = jnp.asarray(h)
        h = _layer(h, gp, gm, "highest")
    return np.asarray(h[:n], np.float64), owner


def nearest_gaps(got, want, owner) -> np.ndarray:
    """For each row of ``got``, the widest gap to the nearest of the
    candidates ``want`` whose ``owner`` is that row."""
    gaps = np.full(got.shape[0], np.inf)
    wide = np.abs(want - got[owner]).max(axis=1)
    np.minimum.at(gaps, owner, wide)
    return gaps


@partial(jax.jit, static_argnames=("spec_key", "precision"))
def _train_step(layers, x, target, lr, spec_key, precision):
    spec = dict(spec_key)
    acts, dps = [], []
    h = x
    for li, (gp, gm) in enumerate(layers):
        if spec["transport_quant"] and li > 0:
            h = adc(h, spec["adc_bits"])
        acts.append(h)
        dp = matmul(h, gp - gm, precision)
        dps.append(dp)
        h = hard_sigmoid(dp)
    err = target - h
    delta = err
    new = list(layers)
    batch = x.shape[0]
    for li in reversed(range(len(layers))):
        gp, gm = layers[li]
        if spec["error_quant"]:
            delta = error_codes(delta, spec["err_bits"])
        local = delta * hard_sigmoid_deriv(dps[li])
        dw = 2.0 * lr * matmul(acts[li].T, local, precision) / batch
        if spec["update_quant"]:
            dw = pulses(dw, spec["max_update"], spec["update_levels"])
        new[li] = (jnp.clip(gp + 0.5 * dw, 0.0, spec["w_max"]),
                   jnp.clip(gm - 0.5 * dw, 0.0, spec["w_max"]))
        delta = matmul(local, (gp - gm).T, precision)
    return tuple(new), err


def train_step(layers, x, target, lr: float, spec: Spec,
               precision: str = "highest"):
    """One step of the paper's stochastic back-propagation on a batch:
    returns (new layers, output error ``target - out``)."""
    return _train_step(tuple(layers), x, target, jnp.float32(lr),
                       spec.key(), precision)


def split_seed(seed: int) -> jnp.ndarray:
    """A seed of any size up to 64 bits as two uint32 words."""
    seed = int(seed) % (1 << 64)
    return jnp.array([seed & 0xFFFFFFFF, seed >> 32], jnp.uint32)


def seed_key(words, stream: int):
    key = jax.random.key(words[0])
    key = jax.random.fold_in(key, words[1])
    return jax.random.fold_in(key, stream)


@partial(jax.jit, static_argnames=("dims", "w_max", "g_init"))
def _initial_layers(words, dims, w_max, g_init):
    key = seed_key(words, 0)
    out = []
    for i, (f, o) in enumerate(zip(dims, dims[1:])):
        kp, km = jax.random.split(jax.random.fold_in(key, i))
        hi = g_init * w_max
        out.append((jax.random.uniform(kp, (f, o), jnp.float32, 0.0, hi),
                    jax.random.uniform(km, (f, o), jnp.float32, 0.0, hi)))
    return tuple(out)


def initial_layers(cfg: dict, seed: int):
    """The paper's step 1, high random resistances: both devices of every
    pair uniform in [0, g_init * w_max), drawn on the device in one call."""
    return _initial_layers(split_seed(seed), tuple(cfg["dims"]),
                           float(cfg["w_max"]), float(cfg["g_init"]))


@partial(jax.jit, static_argnames=("shape", "stream"))
def _uniform_pool(words, shape, stream):
    return jax.random.uniform(seed_key(words, stream), shape, jnp.float32,
                              -0.5, 0.5)


def uniform_pool(seed: int, stream: int, shape: tuple):
    """Inputs and targets: uniform in [-0.5, 0.5), the range of the
    activation and of the command-line tools' data, drawn on the device."""
    return _uniform_pool(split_seed(seed), tuple(shape), stream)
