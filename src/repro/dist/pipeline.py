"""GPipe-style pipeline parallelism over a 1-D mesh axis.

``pipeline_apply`` runs a stage function over ``n_stages`` stacked parameter
slices with microbatches streamed through a ``ppermute`` ring: device ``s``
executes microbatch ``t - s`` at tick ``t``, so the pipe drains in
``n_micro + n_stages - 1`` ticks.  ``serial_reference`` is the numerics
oracle (identical math, no mesh).

This is the *LM-path* (device-mesh) pipeline.  Its chip-level counterpart
is ``repro.sim.fabric.ChipPipeline`` (DESIGN.md §7), which splits a placed
crossbar network across simulated chips with the paper's boundary
quantization and a 1F1B schedule model (`core.hw_model.schedule_1f1b`);
the two share the stage-group discipline but not code — one pipelines jax
computations over devices, the other pipelines placed core stacks over
modeled chips.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def serial_reference(stage: Callable, params, x: jax.Array) -> jax.Array:
    """Apply the ``n_stages`` stacked stages sequentially to all
    microbatches.  x: (n_micro, mb, d)."""
    n_stages = jax.tree.leaves(params)[0].shape[0]
    h = x
    for s in range(n_stages):
        p_s = jax.tree.map(lambda a: a[s], params)
        h = stage(p_s, h)
    return h


def pipeline_apply(stage: Callable, params, x: jax.Array, *, mesh: Mesh,
                   axis_name: str) -> jax.Array:
    """Pipeline ``stage`` over ``axis_name``; params sharded on their leading
    (stage) axis, microbatches replicated in, outputs replicated out."""
    n_stages = mesh.shape[axis_name]
    n_micro = x.shape[0]

    def body(p_shard, x_all):
        # p_shard leaves: (1, ...) — this device's stage slice
        p_s = jax.tree.map(lambda a: a[0], p_shard)
        sid = jax.lax.axis_index(axis_name)
        is_first = sid == 0
        is_last = sid == n_stages - 1
        zero = jnp.zeros(x_all.shape[1:], x_all.dtype)
        outputs = jnp.zeros_like(x_all)
        recv = zero
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        for t in range(n_micro + n_stages - 1):
            feed = x_all[t] if t < n_micro else zero
            inp = jnp.where(is_first, feed, recv)
            out = stage(p_s, inp)
            # device sid holds microbatch t - sid at this tick
            mb = t - sid
            valid = (mb >= 0) & (mb < n_micro) & is_last
            upd = jax.lax.dynamic_update_slice(
                outputs, out[None].astype(outputs.dtype),
                (jnp.clip(mb, 0, n_micro - 1),) + (0,) * (x_all.ndim - 1))
            outputs = jnp.where(valid, upd, outputs)
            recv = jax.lax.ppermute(out, axis_name, perm)
        # replicate the last stage's outputs to every device
        return jax.lax.psum(jnp.where(is_last, outputs, 0.0), axis_name)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(axis_name), P()),
                       out_specs=P(), check_vma=False)
    return fn(params, x)
