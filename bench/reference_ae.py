"""Plain reference of one step of greedy layer-wise autoencoder
pretraining (arXiv:1603.07400, sections III.C-E), built on
`bench/reference.py` alone: it imports nothing of the program.

Hidden layer ``k`` is pretrained as a two-layer network, its encoder and
a temporary decoder back to the encoder's input, whose target is that
input: the representation the frozen encoders ``0 .. k-1`` make of the
network input.  The network input is DAC-driven; each representation
crosses a core boundary, so it is a 3-bit ADC code.  One step is then
`reference.train_step` on (encoder k, decoder k) with the representation
as both input and target; the frozen encoders are not touched.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench import reference as ref

#: the stream of the seed the temporary decoders are drawn from (the
#: encoders take stream 0, the inputs stream 1)
DECODER_STREAM = 3


@partial(jax.jit, static_argnames=("spec_key", "precision", "quantize"))
def _representation(prefix, x, spec_key, precision, quantize):
    spec = dict(spec_key)
    h = x
    for li, (gp, gm) in enumerate(prefix):
        h = ref.hard_sigmoid(ref.matmul(h, gp - gm, precision))
        if spec["transport_quant"] and (quantize or li < len(prefix) - 1):
            h = ref.adc(h, spec["adc_bits"])
    return h


def representation(prefix, x, spec: ref.Spec, precision: str = "highest",
                   quantize: bool = True):
    """What enters the encoder behind the frozen encoders ``prefix``: the
    network input at the first layer, else the last frozen encoder's
    output as 3-bit codes (``quantize=False`` leaves that last one as the
    activation before its ADC)."""
    return _representation(tuple(prefix), x, spec.key(), precision,
                           quantize)


def pretrain_step(prefix, pair, x, lr: float, spec: ref.Spec,
                  precision: str = "highest"):
    """One pretraining step of the (encoder, decoder) ``pair`` behind the
    frozen ``prefix`` on network inputs ``x``: (new pair, reconstruction
    error ``target - out``)."""
    r = representation(prefix, x, spec, precision)
    return ref.train_step(pair, r, r, lr, spec, precision)


@partial(jax.jit, static_argnames=("dims", "w_max", "g_init"))
def _initial_decoders(words, dims, w_max, g_init):
    key = ref.seed_key(words, DECODER_STREAM)
    out = []
    for j, (f, o) in enumerate(zip(dims, dims[1:])):
        kp, km = jax.random.split(jax.random.fold_in(key, j))
        hi = g_init * w_max
        out.append((jax.random.uniform(kp, (o, f), jnp.float32, 0.0, hi),
                    jax.random.uniform(km, (o, f), jnp.float32, 0.0, hi)))
    return tuple(out)


def initial_decoders(cfg: dict, seed: int):
    """One temporary decoder per hidden layer, ``dims[j+1] x dims[j]``,
    both devices of every pair uniform in [0, g_init * w_max), drawn on
    the device from ``seed`` on `DECODER_STREAM`."""
    return _initial_decoders(ref.split_seed(seed), tuple(cfg["dims"]),
                             float(cfg["w_max"]), float(cfg["g_init"]))
