"""Compressed gradient collectives — the paper's narrow-transport discipline
(8-bit sign-magnitude error links, section III.F) applied at the
data-parallel level.

``compressed_grad_mean`` runs inside ``shard_map``: the reduce-scatter leg
averages in bf16, the broadcast leg re-quantizes to int8 with *stochastic*
rounding (unbiased in expectation, tests/test_distribution.py), so an
all-reduce moves ~1/4 the bytes of an f32 ring at a bounded, zero-mean
error.  ``dp_train_step_fn`` wires it into a pure-data-parallel train step.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

INT8_MAX = 127


def _int8_stochastic(x: jax.Array, key: jax.Array) -> jax.Array:
    """Stochastic int8 round-trip: E[deq(quant(x))] == x."""
    scale = jnp.max(jnp.abs(x)) / INT8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    noise = jax.random.uniform(key, x.shape, jnp.float32)
    codes = jnp.clip(jnp.floor(x / scale + noise), -INT8_MAX, INT8_MAX)
    return codes * scale


def compressed_grad_mean(grads, mesh: Mesh, axis_names: tuple[str, ...],
                         *, mode: str = "none",
                         key: jax.Array | None = None):
    """Mean of per-device grads over ``axis_names`` (call inside shard_map).

    mode "none": exact f32 all-reduce.
    mode "bf16": reduce in bf16 (half the bytes, deterministic rounding).
    mode "int8": bf16 reduce-scatter leg + int8 stochastically-rounded
                 broadcast leg (quarter bytes, unbiased).
    """
    axis = tuple(axis_names)
    if mode == "none":
        return jax.tree.map(lambda g: jax.lax.pmean(g, axis), grads)
    if mode == "bf16":
        return jax.tree.map(
            lambda g: jax.lax.pmean(g.astype(jnp.bfloat16), axis)
            .astype(g.dtype), grads)
    if mode != "int8":
        raise ValueError(f"unknown compression mode: {mode!r}")
    if key is None:
        raise ValueError("int8 compression requires a PRNG key")

    leaves, treedef = jax.tree.flatten(grads)
    out = []
    for i, g in enumerate(leaves):
        # reduce-scatter leg in bf16 (deterministic floor of the scheme)
        m = jax.lax.pmean(g.astype(jnp.bfloat16), axis).astype(jnp.float32)
        # broadcast leg: int8 + stochastic rounding (unbiased over keys)
        out.append(_int8_stochastic(m, jax.random.fold_in(key, i))
                   .astype(g.dtype))
    return jax.tree.unflatten(treedef, out)


def farm_reduce_sum(contrib: jax.Array, *, axis_name: str | None = None,
                    chip_axis: int = 0, mode: str = "none",
                    err_bits: int = 8) -> jax.Array:
    """Reconcile per-chip pulse-update contributions into one farm update.

    The chip farm (repro.sim.cluster) trains data-parallel: every chip
    computes a LOCAL batch-summed outer product (Eq. 6) and the host link
    carries the contributions to a single reconciled update — the paper's
    pulse discipline applied once, on the SUM, so the farm's replicas stay
    bitwise in lockstep with a serial chip (DESIGN.md §6).

    ``contrib`` carries the chips on ``chip_axis``.  Outside ``shard_map``
    that axis holds every chip of the farm and is summed away.  Inside
    ``shard_map`` pass ``axis_name``: the axis holds this device's chips,
    which are summed, and the sums are psummed over the mesh axis.

    mode "none": exact f32 sum (the default — farm == serial exactly).
    mode "int8": each chip's contribution rides the host link as 8-bit
                 sign-magnitude codes with its OWN full-scale (paper III.F
                 step 1 per chip) — quarter payload vs f32, error bounded per
                 chip, so a quiet chip's update survives next to a loud
                 one.  Chips are coded one by one before any sum, so a
                 device that holds several chips codes each of them.
    """
    if mode == "int8":
        from repro.core import quantization as q

        def code(g):
            return q.error_quantize(g, err_bits).dequantize()

        contrib = jax.vmap(code, in_axes=chip_axis,
                           out_axes=chip_axis)(contrib)
    elif mode != "none":
        raise ValueError(f"unknown farm reduction mode: {mode!r}")
    total = jnp.sum(contrib, axis=chip_axis)
    if axis_name is not None:
        return jax.lax.psum(total, axis_name)
    return total


def farm_max(x: jax.Array, *, axis_name: str | None = None,
             chip_axis: int = 0) -> jax.Array:
    """Farm-wide max (keeps the reduced axis as size 1 outside shard_map).

    Used for the shared error full-scale: the paper's 8-bit error ADC has
    ONE full-scale per tensor, so the farm must agree on max|delta| across
    all chips before quantizing — otherwise each chip would discretize its
    shard on a different grid and the replicas would drift from the serial
    reference."""
    if axis_name is not None:
        return jax.lax.pmax(x, axis_name)
    return jnp.max(x, axis=chip_axis, keepdims=True)


def dp_train_step_fn(loss_fn: Callable, opt, mesh: Mesh, *,
                     compression: str = "int8") -> Callable:
    """Jit'd pure-DP train step with compressed gradient all-reduce.

    ``loss_fn(params, batch) -> (loss, aux)``; ``opt`` follows
    repro.optim.Optimizer (``update(grads, state, params, step=...)``).
    Returns ``step(params, opt_state, batch, step, key) ->
    (params, opt_state, loss)`` with params/opt replicated and the batch
    sharded over the mesh's axes.
    """
    axis = tuple(mesh.axis_names)

    def shard_body(params, opt_state, batch, step, key):
        (loss, _), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        grads = compressed_grad_mean(grads, mesh, axis, mode=compression,
                                     key=key)
        loss = jax.lax.pmean(loss, axis)
        new_params, new_opt = opt.update(grads, opt_state, params, step=step)
        return new_params, new_opt, loss

    batch_spec = P(axis if len(axis) > 1 else axis[0])
    fn = jax.shard_map(shard_body, mesh=mesh,
                       in_specs=(P(), P(), batch_spec, P(), P()),
                       out_specs=(P(), P(), P()),
                       check_vma=False)
    return jax.jit(fn, donate_argnums=(0, 1))
