"""Compiled whole-step execution: the only way the simulator runs
(DESIGN.md §8).

The paper's chip has no host in the loop: the whole network step is a
fixed schedule burned into hardware.  This module is that schedule for
the *simulator*: each hot loop (recognition wave, training step, farm
step, serving session) is ONE jitted XLA program whose stage loop is a
``lax.scan`` over the padded ragged stage stack
(`repro.sim.placer.StageStacks`), with

  * conductance stacks DONATED — training updates the buffers in place,
    no per-step copy of the network's weights;
  * the per-stage training body fused into one Pallas megakernel
    (`kernels/ops.crossbar_train_stacked`): bwd-error + dw + pulse update
    read each conductance tile from VMEM once;
  * `PhaseCounters` accounting carried through the scan as traced integer
    accumulators, so counters come back in ONE device->host transfer per
    step instead of one per stage (the per-stage NoC link records are
    compile-time constants of the placement — the static routing schedule
    — and are replayed host-side from `StageStacks` metadata).

Every program takes an optional leading *chip* axis: the serial chip is
the ``C == 1`` special case of the farm, so both execute the same traced
code and cannot drift apart.  Numerics match the plain reference
(`core.crossbar.mlp_forward` / `paper_backprop_step`) within float
re-association, and the padded layout is BITWISE padding-invariant (see
`StageStacks`), which is what keeps the pipeline fabric's slice-vs-serial
pins exact.  Given a farm's ``mesh``, a program runs the same body under
``shard_map``, each device on its own replicas.

Compilation is memoized by ``jax.jit`` on (static config, operand shapes):
two chips with the same topology and batch share one executable.  The
module counts traces (`trace_counts`) so tests can assert exactly one
compilation per (topology, batch) shape.
"""
from __future__ import annotations

import os
from collections import Counter
from functools import partial, wraps
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import quantization as q
from repro.core.crossbar import F32, hard_sigmoid, hard_sigmoid_deriv
from repro.kernels import ops as kernel_ops
from repro.sim import spans


#: the mesh axis a sharded farm splits its replicas over
FARM_AXIS = "chips"


def kernel_body_enabled() -> bool:
    """Whether the compiled scan bodies dispatch the fused Pallas kernels
    (`crossbar_train_stacked` and friends).

    True on a real TPU backend (the kernels lower natively).  Otherwise
    the bodies use the bitwise-reference jnp math: on CPU the kernels only
    exist in *interpret mode*, whose per-call emulation overhead is the
    very dispatch tax the compiled step removes (~10-35x a plain XLA
    contraction, growing with the core stack).
    ``REPRO_SIM_FORCE_KERNELS=1`` or ``=0`` overrides the backend's choice:
    tests run the kernel-in-scan path on CPU, and ``chip_smoke.py`` times
    the jnp body against the kernel body on the chip.  The flag is
    captured into `ChipConfig`, so flipping it mid-process compiles a
    fresh program."""
    force = os.environ.get("REPRO_SIM_FORCE_KERNELS")
    if force in ("0", "1"):
        return force == "1"
    return jax.default_backend() == "tpu"

# ---------------------------------------------------------------------------
# Trace accounting (one compile per (program, config, shapes))
# ---------------------------------------------------------------------------

_TRACE_COUNTS: Counter = Counter()


def _mark(program: str, cfg, *shapes) -> None:
    """Count one trace of ``program`` — runs at trace time only, so the
    per-key count equals the number of XLA compilations."""
    _TRACE_COUNTS[(program, cfg) + tuple(shapes)] += 1


def trace_counts() -> dict:
    """Snapshot of the compile counter: {(program, cfg, *shapes): traces}."""
    return dict(_TRACE_COUNTS)


def reset_trace_counts() -> None:
    """Clear the compile counter (tests only — compiled executables stay
    cached in jax, so a re-run after reset shows zero new traces)."""
    _TRACE_COUNTS.clear()


class ChipConfig(NamedTuple):
    """Static (hashable) configuration of a compiled chip program: the
    `StageStacks` envelope geometry plus the `CrossbarSpec` constants the
    traced code branches on.  ``Ts``/``fan_in``/``fan_out`` are the
    per-stage core counts and widths the programs cut their outputs to."""
    S: int
    T_max: int
    r_max: int
    c_max: int
    rows: int
    cols: int
    L: int
    N_pad: int
    out_dim: int
    Ts: tuple[int, ...]
    fan_in: tuple[int, ...]
    fan_out: tuple[int, ...]
    transport_quant: bool
    adc_bits: int
    error_quant: bool
    err_bits: int
    update_quant: bool
    max_update: float
    update_levels: int
    w_max: float
    use_kernels: bool = False
    conv: tuple = ()      # `mapping.ConvShape` of each leading conv stage


def chip_config(stacks, spec) -> ChipConfig:
    """Build the static program config from a `StageStacks` + spec."""
    return ChipConfig(
        use_kernels=kernel_body_enabled(),
        S=stacks.S, T_max=stacks.T_max, r_max=stacks.r_max,
        c_max=stacks.c_max, rows=stacks.rows, cols=stacks.cols,
        L=stacks.L, N_pad=stacks.N_pad, out_dim=stacks.out_dim,
        Ts=stacks.Ts, fan_in=stacks.fan_in, fan_out=stacks.fan_out,
        transport_quant=bool(spec.transport_quant),
        adc_bits=int(spec.adc_bits),
        error_quant=bool(spec.error_quant), err_bits=int(spec.err_bits),
        update_quant=bool(spec.update_quant),
        max_update=float(spec.max_update),
        update_levels=int(spec.update_levels), w_max=float(spec.w_max),
        conv=stacks.conv)


# ---------------------------------------------------------------------------
# Scan bodies (shared, chip-axis always present: serial chip == C=1 farm)
# ---------------------------------------------------------------------------

def _embed(h: jax.Array, L: int) -> jax.Array:
    """(C, M, W) activation -> (C, M, L) padded input vector: bias slot 0
    (always zero), payload in lanes [1, W], zeros beyond."""
    C, M, W = h.shape
    out = jnp.zeros((C, M, L), jnp.float32)
    return out.at[:, :, 1:W + 1].set(h)


def _fwd_dispatch(xs, gp_s, gm_s, cfg: "ChipConfig"):
    """Stacked forward dispatch bridging the serial/farm stack ranks: the
    data always carries a chip axis (serial == C=1), the conductances only
    on the farm path (rank 4).  Per-core numerics are identical either
    way — batched over the core axis — so the two ranks cannot drift.
    Kernel vs reference-math body per `kernel_body_enabled` (static)."""
    if cfg.use_kernels:
        if gp_s.ndim == 3:
            return kernel_ops.crossbar_fwd_stacked(xs[0], gp_s, gm_s)[None]
        return kernel_ops.crossbar_fwd_stacked(xs, gp_s, gm_s)
    w = (gp_s - gm_s).astype(jnp.float32)
    if gp_s.ndim == 3:
        return jnp.einsum("ctmk,tkn->ctmn", xs.astype(jnp.float32), w,
                          precision=F32)
    return jnp.einsum("ctmk,ctkn->ctmn", xs.astype(jnp.float32), w,
                      precision=F32)


def _stage_dp(h_ext, gp_s, gm_s, in_s, dp_s, cfg: ChipConfig) -> jax.Array:
    """One stage's exact-aggregated dot products from the padded input.

    The Fig.-14 sub-neuron aggregation is evaluated as a SEQUENTIAL sum
    over the static ``r_max`` fan-in tiles (trailing zero terms are exact
    no-ops), which makes the result independent of the envelope the stage
    is padded into — the §8 bitwise invariance."""
    C, M = h_ext.shape[0], h_ext.shape[1]
    xs = jnp.moveaxis(h_ext[:, :, in_s], 1, 2)        # (C, T_max, M, rows)
    ys = _fwd_dispatch(xs, gp_s, gm_s, cfg)
    ys_flat = jnp.concatenate(
        [jnp.moveaxis(ys, 1, 2).reshape(C, M, cfg.T_max * cfg.cols),
         jnp.zeros((C, M, 1), jnp.float32)], axis=2)
    dp = ys_flat[:, :, dp_s[0]]
    for i in range(1, cfg.r_max):
        dp = dp + ys_flat[:, :, dp_s[i]]
    return dp                                          # (C, M, N_pad)


def _forward_scan(gp, gm, x, idx, quantize_tail, cfg: ChipConfig):
    """Wave through all stages as one ``lax.scan``.

    Returns (acts (S, C, M, L), dps (S, C, M, N_pad), tail h (C, M, N_pad),
    counters).  ``quantize_tail`` is a traced scalar bool (no recompile
    when a pipeline slice toggles it)."""
    C, M = x.shape[0], x.shape[1]
    h0 = _embed(x, cfg.L)
    s_ix = jnp.arange(cfg.S)
    quant_out = (s_ix < cfg.S - 1) | (quantize_tail & (s_ix == cfg.S - 1))

    def body(carry, per):
        h_ext, cnt = carry
        gp_s, gm_s, in_s, dp_s, valid_s, quant_s, cores_s = per
        dp = _stage_dp(h_ext, gp_s, gm_s, in_s, dp_s, cfg)
        h = hard_sigmoid(dp)
        if cfg.transport_quant:
            hq = q.adc_quantize(h, cfg.adc_bits) * valid_s[None, None, :]
            h_out = jnp.where(quant_s, hq, h)
        else:
            h_out = h
        cnt = cnt + jnp.array([M, 0], jnp.int32) \
            + jnp.array([0, M], jnp.int32) * cores_s
        return (_embed(h_out, cfg.L), cnt), (h_ext, dp)

    (h_last, cnt), (acts, dps) = jax.lax.scan(
        body, (h0, jnp.zeros(2, jnp.int32)),
        (gp, gm, idx["in_idx"], idx["dp_idx"], idx["valid_out"], quant_out,
         idx["core_counts"]))
    return acts, dps, h_last[:, :, 1:cfg.N_pad + 1], cnt


def _stage_update(gp_s, gm_s, xs, ds, cfg: ChipConfig, lr_eff):
    """One stage's bwd + update on its own chip (the serial chip, a
    pipeline slice, a conv stage): the error through the conductances
    ``dxs`` (C, T, M, rows) and the pulse-updated stacks.  ``xs`` (C, T,
    M, rows) are the cores' inputs, ``ds`` (C, T, M, cols) their local
    errors; the conductances carry the chip axis only on the farm
    path."""
    serial = gp_s.ndim == 3          # conductances without a chip axis
    if cfg.use_kernels:
        kxs, kds = (xs[0], ds[0]) if serial else (xs, ds)
        if cfg.update_quant:
            # fused megakernel: bwd + dw + pulse, conductances read
            # once (the compiled step's per-stage training body).
            # The kernel's lr is a compile-time constant, so the
            # traced lr_eff rides in as a pre-scale on x — x only
            # feeds the dw contraction here (compute_y=False).
            _, dxs, gp2, gm2 = kernel_ops.crossbar_train_stacked(
                gp_s, gm_s, kxs * lr_eff, kds, lr=1.0,
                max_dw=cfg.max_update,
                levels=cfg.update_levels, w_max=cfg.w_max,
                compute_y=False)
        else:
            dxs = kernel_ops.crossbar_bwd_stacked(kds, gp_s, gm_s)
            dw = 2.0 * lr_eff * jnp.einsum("tmk,tmn->tkn", kxs, kds,
                                           precision=F32)
            gp2 = jnp.clip(gp_s + 0.5 * dw, 0.0, cfg.w_max)
            gm2 = jnp.clip(gm_s - 0.5 * dw, 0.0, cfg.w_max)
        if serial:
            dxs = dxs[None]
        return dxs, gp2, gm2
    # reference-math body (same fused structure, one read of w):
    # per-chip pulse applied locally, exactly the megakernel math.
    w = (gp_s - gm_s).astype(jnp.float32)
    bspec = "tkn" if serial else "ctkn"
    dxs = jnp.einsum(f"ctmn,{bspec}->ctmk", ds, w, precision=F32)
    dwe = "ctmk,ctmn->tkn" if serial else "ctmk,ctmn->ctkn"
    dw = 2.0 * lr_eff * jnp.einsum(dwe, xs, ds, precision=F32)
    if cfg.update_quant:
        dw = q.pulse_discretize(dw, cfg.max_update,
                                cfg.update_levels, None)
    gp2 = jnp.clip(gp_s + 0.5 * dw, 0.0, cfg.w_max)
    gm2 = jnp.clip(gm_s - 0.5 * dw, 0.0, cfg.w_max)
    return dxs, gp2, gm2


def _backward_scan(gp, gm, acts, dps, delta, idx, cfg: ChipConfig,
                   lr_eff, reconcile: str | None,
                   axis_name: str | None = None, first: int = 0):
    """Bwd + update phases as one reversed ``lax.scan``.

    ``lr_eff`` (lr / global batch) is a TRACED scalar — an lr schedule
    reuses the same executable instead of recompiling per step (the
    one-compile-per-(topology, batch) contract).  ``reconcile is None``
    is the per-chip pulse path (the serial chip and pipeline slices): the
    fused megakernel writes each stack's pulse update in place.
    ``reconcile in ("none", "int8")`` is the farm's data-parallel path:
    local outer products, `farm_reduce_sum` reconciliation INSIDE the
    trace, the pulse discretized once on the sum and broadcast to every
    replica.  ``axis_name`` is the mesh axis when the program runs under
    ``shard_map`` with this device's chips only: the error full-scale is
    then agreed by `farm_max` and the outer products are psummed by
    `farm_reduce_sum` over it.  ``first`` (static) is the first trained
    stage: the scan runs over stages ``first ..`` only, and the stacks of
    the stages below are never written, so a frozen prefix stays bitwise
    as it was (layer-wise pretraining).  Returns (new gp, new gm, upstream
    delta — the error entering stage ``first`` — and counters)."""
    from repro.dist.collectives import farm_max, farm_reduce_sum

    C, M = delta.shape[0], delta.shape[1]
    B_total = C * M

    def body(carry, per):
        delta, cnt = carry
        gp_s, gm_s, act_s, dp_s, in_s, ds_s, fold_s, prev_s, cores_s = per
        if cfg.error_quant:
            # III.F step 1 with the farm-shared full-scale: quantizing the
            # flattened global tensor IS max-abs over every chip's shard.
            flat = delta.reshape(B_total, -1)
            full = (None if axis_name is None else
                    farm_max(jnp.max(jnp.abs(flat)), axis_name=axis_name))
            delta = (q.error_quantize(flat, cfg.err_bits, full_scale=full)
                     .dequantize().reshape(C, M, -1))
        local = delta * hard_sigmoid_deriv(dp_s)
        local_ext = jnp.concatenate(
            [local, jnp.zeros((C, M, 1), jnp.float32)], axis=2)
        ds = jnp.moveaxis(local_ext[:, :, ds_s], 1, 2)  # (C, T_max, M, cols)
        xs = jnp.moveaxis(act_s[:, :, in_s], 1, 2)      # (C, T_max, M, rows)

        if reconcile is None:
            dxs, gp2, gm2 = _stage_update(gp_s, gm_s, xs, ds, cfg, lr_eff)
        else:
            if cfg.use_kernels:
                dxs = kernel_ops.crossbar_bwd_stacked(ds, gp_s, gm_s)
                dw_local = kernel_ops.crossbar_dw_stacked(xs, ds)
            else:
                w = (gp_s - gm_s).astype(jnp.float32)
                dxs = jnp.einsum("ctmn,ctkn->ctmk", ds, w, precision=F32)
                dw_local = jnp.einsum("ctmk,ctmn->ctkn", xs, ds,
                                      precision=F32)
            dw = 2.0 * lr_eff * farm_reduce_sum(dw_local, mode=reconcile,
                                                axis_name=axis_name)
            if cfg.update_quant:
                dw = q.pulse_discretize(dw, cfg.max_update,
                                        cfg.update_levels, None)
            gp2 = jnp.clip(gp_s + 0.5 * dw[None], 0.0, cfg.w_max)
            gm2 = jnp.clip(gm_s - 0.5 * dw[None], 0.0, cfg.w_max)

        # fan-in fold: group i sums its fan-out tiles SEQUENTIALLY over
        # the static c_max (padding-invariant, like _stage_dp).
        dxs_ext = jnp.concatenate(
            [dxs, jnp.zeros((C, 1, M, cfg.rows), jnp.float32)], axis=1)
        dxg = dxs_ext[:, fold_s[:, 0]]
        for j in range(1, cfg.c_max):
            dxg = dxg + dxs_ext[:, fold_s[:, j]]
        dxg_flat = jnp.concatenate(
            [jnp.moveaxis(dxg, 1, 2).reshape(C, M, cfg.r_max * cfg.rows),
             jnp.zeros((C, M, 1), jnp.float32)], axis=2)
        delta_prev = dxg_flat[:, :, prev_s]
        cnt = cnt + jnp.array([M, 0, M, 0], jnp.int32) \
            + jnp.array([0, M, 0, M], jnp.int32) * cores_s
        return (delta_prev, cnt), (gp2, gm2)

    per = (gp, gm, acts, dps, idx["in_idx"], idx["ds_idx"], idx["fold_idx"],
           idx["prev_idx"], idx["core_counts"])
    if first:
        per = tuple(a[first:] for a in per)
    (delta_fin, cnt), (gp_new, gm_new) = jax.lax.scan(
        body, (delta, jnp.zeros(4, jnp.int32)), per, reverse=True)
    if first:
        gp_new, gm_new = gp.at[first:].set(gp_new), gm.at[first:].set(gm_new)
    return gp_new, gm_new, delta_fin, cnt


# ---------------------------------------------------------------------------
# Conv stages (DESIGN.md "Conv stages")
# ---------------------------------------------------------------------------
#
# A conv network's first ``len(cfg.conv)`` stages are conv layers, each one
# core stepped over every position of its feature map.  They do not ride
# the uniform stage scan, which would pad every dense stage to the conv
# stages' positions: each runs as its own step of the same program, its
# positions folded into the rows of the same stacked kernels, and the
# dense stages after them run the scan on their own part of the envelope.

#: the dense stages' index maps, cut past the conv stages
_DENSE_IDX = ("in_idx", "ds_idx", "dp_idx", "fold_idx", "prev_idx",
              "valid_out", "core_counts")


def _dense_part(gp, gm, idx, cfg: ChipConfig):
    """The dense stages after the conv stages as an envelope of their
    own: (gp, gm, index maps, config)."""
    n = len(cfg.conv)
    sub = cfg._replace(S=cfg.S - n, Ts=cfg.Ts[n:], fan_in=cfg.fan_in[n:],
                       fan_out=cfg.fan_out[n:], conv=())
    return gp[n:], gm[n:], {k: idx[k][n:] for k in _DENSE_IDX}, sub


def _pool(a, s, cfg: ChipConfig):
    """The router's pool of one conv stage: (C, M*P, out) activations ->
    (C, M, pooled size).  Each activation leaves its core as a 3-bit
    code, a window's codes are summed and the next core takes the sum
    over the window exactly (`core.conv.pool`)."""
    Ho, Wo = s.out_hw
    p = s.pool
    win = (a.shape[0], -1, Ho // p, p, Wo // p, p, s.out)
    if cfg.transport_quant:
        levels = 2 ** cfg.adc_bits - 1
        codes = jnp.round((q.adc_quantize(a, cfg.adc_bits) + 0.5) * levels)
        v = codes.reshape(win).sum((3, 5)) / (p * p * levels) - 0.5
    else:
        v = a.reshape(win).sum((3, 5)) / (p * p)
    return v.reshape(v.shape[0], v.shape[1], -1)


def _unpool(e, s):
    """The pool's backward pass: (C, M, pooled size) errors -> (C, M*P,
    out), each lane of a window taking the window's error over the
    window's size."""
    C, M = e.shape[0], e.shape[1]
    Ho, Wo = s.out_hw
    p = s.pool
    e = e.reshape(C, M, Ho // p, 1, Wo // p, 1, s.out) / (p * p)
    e = jnp.broadcast_to(e, (C, M, Ho // p, p, Wo // p, p, s.out))
    return e.reshape(C, M * s.positions, s.out)


def _conv_forward(gp, gm, x, idx, cfg: ChipConfig):
    """The conv stages' wave on chip-stacked ``x`` (C, M, in size): each
    stage's patches gathered by its position-indexed map, its core
    stepped over every position as ONE kernel call with the positions
    folded into the rows (M*P rows), then the router's pool.  Returns
    (patches (C, 1, M*P, rows) and dot products (C, M*P, out) of each
    stage, the last pooled map (C, M, pooled size), conv core steps, and
    per stage [core steps, routed codes, pool sums])."""
    if gp.ndim != 4:
        raise NotImplementedError("conv stages run on the serial chip")
    C, M = x.shape[0], x.shape[1]
    h, xs_all, dps, per = x, [], [], []
    with jax.named_scope("repro.conv"):
        for l, s in enumerate(cfg.conv):
            h_ext = jnp.concatenate(
                [jnp.zeros((C, M, 1), jnp.float32), h], axis=2)
            xs = h_ext[:, :, idx["conv_patch"][l]].reshape(
                C, 1, M * s.positions, cfg.rows)
            dp = _fwd_dispatch(xs, gp[l, :1], gm[l, :1], cfg)[:, 0, :,
                                                                :s.out]
            h = _pool(hard_sigmoid(dp), s, cfg)
            xs_all.append(xs)
            dps.append(dp)
            per += [dp.shape[1], dp.shape[1] * dp.shape[2],
                    h.shape[1] * h.shape[2] if s.pool > 1 else 0]
    return xs_all, dps, h, sum(per[0::3]), per


def _conv_backward(gp, gm, xs_all, dps, delta, idx, cfg: ChipConfig,
                   lr_eff):
    """Bwd + update of the conv stages, the last first, from ``delta``
    (C, M, pooled size), the error entering the last stage's pooled map:
    the pool's error split over its window, 8-bit codes, the local error
    through the kernel's derivative, and the fused training body on the
    stage's core (`_stage_update`) with the positions folded into the
    rows, so its outer-product sum runs over rows and positions.  The
    error into the stage below is col2im of the core-line errors, a
    sequential sum over the transposed map; none is formed below the
    first stage.  Returns each stage's updated (gp, gm) core stack."""
    C, M = delta.shape[0], delta.shape[1]
    new = [None] * len(cfg.conv)
    with jax.named_scope("repro.conv"):
        for l in reversed(range(len(cfg.conv))):
            s = cfg.conv[l]
            e = _unpool(delta, s)
            if cfg.error_quant:
                e = (q.error_quantize(e.reshape(C * M, -1), cfg.err_bits)
                     .dequantize().reshape(e.shape))
            local = e * hard_sigmoid_deriv(dps[l])
            ds = jnp.pad(local, ((0, 0), (0, 0), (0, cfg.cols - s.out)))
            dxs, gp2, gm2 = _stage_update(gp[l, :1], gm[l, :1], xs_all[l],
                                          ds[:, None], cfg, lr_eff)
            # the kernel stays an operation of its own, not fused into
            # the write of its stacks into the envelope
            new[l] = jax.lax.optimization_barrier((gp2, gm2))
            if l:
                dx = jnp.concatenate(
                    [dxs[:, 0].reshape(C, M, s.positions * cfg.rows),
                     jnp.zeros((C, M, 1), jnp.float32)], axis=2)
                ci = idx["conv_col2im"][l]
                delta = dx[:, :, ci[:, 0]]
                for j in range(1, s.k * s.k):
                    delta = delta + dx[:, :, ci[:, j]]
    return new


def _conv_infer(gp, gm, x, idx, cfg: ChipConfig):
    """`_infer` of a conv network: the conv stages, then the dense
    stages' scan on the last pooled map.  The counters are the wave's
    [slots, core steps] followed by each conv stage's [core steps,
    routed codes, pool sums]."""
    _, _, h, steps, per = _conv_forward(gp, gm, x, idx, cfg)
    gp_d, gm_d, idx_d, cfg_d = _dense_part(gp, gm, idx, cfg)
    _, dps, _, cnt = _forward_scan(gp_d, gm_d, h, idx_d, jnp.asarray(False),
                                   cfg_d)
    cnt = jnp.concatenate([cnt + steps, jnp.asarray(per, jnp.int32)])
    return hard_sigmoid(dps[-1])[:, :, :cfg.out_dim], cnt


def _conv_train(gp, gm, x, target, idx, cfg: ChipConfig, lr_eff):
    """`_train` of a conv network: the conv stages' wave, the dense
    stages' forward and reversed bwd/update scans on their own part of
    the envelope, then the conv stages' bwd/update from the error the
    dense scan hands back.  Counters as `_conv_infer`'s, and the bwd
    counters with the conv stages' steps added."""
    n = len(cfg.conv)
    xs_all, cdps, h, steps, per = _conv_forward(gp, gm, x, idx, cfg)
    gp_d, gm_d, idx_d, cfg_d = _dense_part(gp, gm, idx, cfg)
    acts, dps, _, fcnt = _forward_scan(gp_d, gm_d, h, idx_d,
                                       jnp.asarray(False), cfg_d)
    out = hard_sigmoid(dps[-1])
    tpad = jnp.zeros(out.shape, jnp.float32)
    delta0 = tpad.at[:, :, :target.shape[2]].set(target) - out
    gp_d2, gm_d2, d_in, bcnt = _backward_scan(
        gp_d, gm_d, acts, dps, delta0, idx_d, cfg_d, lr_eff, None)
    new = _conv_backward(gp, gm, xs_all, cdps,
                         d_in[:, :, :cfg.conv[-1].pooled_size], idx, cfg,
                         lr_eff)
    gp2, gm2 = gp.at[n:].set(gp_d2), gm.at[n:].set(gm_d2)
    for l, (p, m) in enumerate(new):
        gp2, gm2 = gp2.at[l, :1].set(p), gm2.at[l, :1].set(m)
    fcnt = jnp.concatenate([fcnt + steps, jnp.asarray(per, jnp.int32)])
    return gp2, gm2, delta0[:, :, :cfg.out_dim], fcnt, bcnt + steps


def _stage_views(g, cfg: ChipConfig) -> tuple[jax.Array, ...]:
    """Per-stage conductance stacks cut from the padded envelope, in the
    layout the simulator objects keep: ``(T_s, rows, cols)`` from the
    serial ``(S, T_max, rows, cols)`` stacks, ``(C, T_s, rows, cols)``
    from the farm's ``(S, C, T_max, rows, cols)``.  Returned as outputs of
    the same program, each is its own buffer: donating the envelope to
    the next step leaves them readable."""
    return tuple(g[s][..., :cfg.Ts[s], :, :] for s in range(cfg.S))


class Stepped(tuple):
    """A training program's results, ``(gp', gm', ...)``, carrying
    ``views``: ``(views_plus, views_minus)``, the updated stacks cut to
    each stage's cores inside the same program (`_stage_views`).  A tuple
    rebuilt from it (``out[2:]``, ``(a, b) + out[2:]``) is a plain tuple
    and carries no views, so views never outlive the stacks they were cut
    from."""

    def __new__(cls, results, views):
        self = super().__new__(cls, results)
        self.views = tuple(views)
        return self


def _returns_views(n: int):
    """Wrap a jitted training program that returns ``n`` results followed
    by its two view tuples: the call returns the ``n`` results as a
    `Stepped` carrying the views.  The program keeps its name (trace
    readers match it) and its ``lower``."""
    def wrap(program):
        @wraps(program)
        def call(*args, **kw):
            out = program(*args, **kw)
            return Stepped(out[:n], out[n:])
        call.lower = program.lower
        return call
    return wrap


@partial(jax.jit, static_argnames=("cfg",))
def stage_views(gp, gm, cfg: ChipConfig):
    """Compiled cut of a padded envelope pair into per-stage
    ``(views_plus, views_minus)`` (`_stage_views`): one launch."""
    return _stage_views(gp, cfg), _stage_views(gm, cfg)


def kept_views(out, cfg: ChipConfig):
    """The per-stage ``(views_plus, views_minus)`` a simulator object keeps
    after training result ``out``: the views its program cut when ``out``
    is the program's own `Stepped`, otherwise (a result rebuilt around
    other stacks) `stage_views` of ``out[0]``/``out[1]``.  Either way the
    kept state is the stacks ``out`` holds."""
    if isinstance(out, Stepped):
        return out.views
    return stage_views(out[0], out[1], cfg)


# ---------------------------------------------------------------------------
# Jitted entry points
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg",))
def chip_forward(gp, gm, x, idx, quantize_tail, cfg: ChipConfig):
    """Compiled recognition/training wave: (acts, dps, tail h, counters).

    ``acts`` and ``dps`` are tuples of S per-stage arrays already cut to
    each stage's widths — inputs ``(..., M, fan_in[s])`` without the bias
    slot, dot products ``(..., M, fan_out[s])`` — and ``h`` is
    ``(..., M, out_dim)``.  ``x`` is chip-stacked (C, M, fan_in) or plain
    (M, fan_in) — the serial case rank-bridges inside the program and
    returns arrays without the chip axis.  Counters: int32 [fwd_slots,
    fwd_core_steps] per chip."""
    _mark("chip_forward", cfg, x.shape)
    _dense_only(cfg, "chip_forward")
    serial = x.ndim == 2
    acts, dps, h, cnt = _forward_scan(
        gp, gm, x[None] if serial else x, idx, quantize_tail, cfg)
    if serial:
        acts, dps, h = acts[:, 0], dps[:, 0], h[0]
    return (tuple(acts[s][..., 1:cfg.fan_in[s] + 1] for s in range(cfg.S)),
            tuple(dps[s][..., :cfg.fan_out[s]] for s in range(cfg.S)),
            h[..., :cfg.out_dim], cnt)


def _dense_only(cfg: ChipConfig, program: str) -> None:
    """Refuse a conv network in a program that runs stage slices (the
    pipeline's): conv stages run whole, in `chip_train`/`chip_infer`."""
    if cfg.conv:
        raise NotImplementedError(f"{program} runs dense stages only; a "
                                  f"conv network runs chip_train/chip_infer")


def _infer(gp, gm, x, idx, cfg: ChipConfig):
    """Recognition wave on chip-stacked ``x`` (C, M, fan_in): (out (C, M,
    out_dim), counters)."""
    if cfg.conv:
        return _conv_infer(gp, gm, x, idx, cfg)
    _, dps, _, cnt = _forward_scan(gp, gm, x, idx, jnp.asarray(False), cfg)
    return hard_sigmoid(dps[-1])[:, :, :cfg.out_dim], cnt


def _train(gp, gm, x, target, idx, cfg: ChipConfig, lr_eff, reconcile,
           axis_name: str | None = None, first: int = 0):
    """Forward wave and reversed bwd/update scan on chip-stacked ``x`` (C,
    M, fan_in) and ``target``: (gp', gm', err (C, M, out_dim), fwd
    counters, bwd counters).  Stages below ``first`` stay frozen
    (`_backward_scan`).  ``target is None`` is the autoencoder target of
    layer-wise pretraining: the activation entering stage ``first`` as
    the wave carried it (the network input at stage 0, the 3-bit codes
    after), taken inside the program.  A conv network (``cfg.conv``)
    trains by `_conv_train`, supervised and on the serial chip only."""
    if cfg.conv:
        if reconcile is not None or first or target is None:
            raise NotImplementedError(
                "a conv network trains supervised on the serial chip")
        return _conv_train(gp, gm, x, target, idx, cfg, lr_eff)
    acts, dps, _, fcnt = _forward_scan(
        gp, gm, x, idx, jnp.asarray(False), cfg)
    out = hard_sigmoid(dps[-1])
    if target is None:
        tpad = acts[first][:, :, 1:]     # zero beyond the stage's fan-in
    else:
        C, M = x.shape[0], x.shape[1]
        tpad = jnp.zeros((C, M, cfg.N_pad), jnp.float32)
        tpad = tpad.at[:, :, :target.shape[2]].set(target)
    delta0 = tpad - out
    gp2, gm2, _, bcnt = _backward_scan(gp, gm, acts, dps, delta0, idx, cfg,
                                       lr_eff, reconcile, axis_name, first)
    return gp2, gm2, delta0[:, :, :cfg.out_dim], fcnt, bcnt


#: how a farm program splits its operands over the farm's mesh, where
#: each device runs its own replicas under ``shard_map`` (so the stacked
#: kernels run per device on its chip slice, and no custom call is ever
#: partitioned by the compiler): the envelope's chip axis (axis 1), the
#: rows of the batch (and the per-stage views, chip-first), and what
#: every device holds alike
_CHIPS, _ROWS, _ALL = P(None, FARM_AXIS), P(FARM_AXIS), P()


@partial(jax.jit, static_argnames=("cfg", "mesh"))
def chip_infer(gp, gm, x, idx, cfg: ChipConfig, mesh=None):
    """Compiled recognition wave -> (out, counters).

    ``x`` is chip-stacked (C, M, fan_in) or plain (M, fan_in) — the
    serial case is bridged to C == 1 INSIDE the program, so the caller
    pays no per-call reshape dispatches.  With the farm's ``mesh``, ``x``
    is the global batch (M, fan_in), its rows split over the devices, and
    ``out`` is (M, out_dim) split the same way, each device running its
    own replicas under ``shard_map``."""
    if mesh is not None:
        _mark("chip_infer", cfg, x.shape, tuple(mesh.shape.items()))

        def body(gp, gm, x, idx):
            out, cnt = _infer(gp, gm, x.reshape(gp.shape[1], -1, x.shape[-1]),
                              idx, cfg)
            return out.reshape(-1, cfg.out_dim), cnt

        return jax.shard_map(body, mesh=mesh,
                             in_specs=(_CHIPS, _CHIPS, _ROWS, _ALL),
                             out_specs=(_ROWS, _ALL),
                             check_vma=False)(gp, gm, x, idx)
    _mark("chip_infer", cfg, x.shape)
    serial = x.ndim == 2
    out, cnt = _infer(gp, gm, x[None] if serial else x, idx, cfg)
    return (out[0] if serial else out), cnt


@_returns_views(5)
@partial(jax.jit, static_argnames=("cfg", "reconcile", "mesh", "first"),
         donate_argnums=(0, 1))
def chip_train(gp, gm, x, target, idx, cfg: ChipConfig, lr_eff=0.1,
               reconcile: str | None = None, mesh=None, first: int = 0):
    """Compiled training step — forward wave + reversed bwd/update scan in
    ONE donated program.  Returns the `Stepped` (gp', gm', err, fwd
    counters, bwd counters); the conductance stacks update in place
    (donation), and its ``views`` are gp'/gm' cut to each stage's cores,
    the layout the simulator objects keep (`kept_views`).
    ``x``/``target`` rank-bridge like :func:`chip_infer`; ``lr_eff`` is a
    traced scalar (an lr schedule reuses one executable).

    With the farm's ``mesh`` every device runs its replicas' slice of the
    step under ``shard_map``: ``x``/``target`` are the global batch, rows split
    over the devices; the error full-scale and the reconciled outer
    products are agreed across devices inside the program; ``err`` comes
    back (M, out_dim) and the views split by chips like the stacks.

    Layer-wise pretraining (serial chip): ``first`` (static) is the first
    trained stage, the stages below it a frozen prefix whose stacks are
    never written, and ``target=None`` takes the autoencoder target, the
    activation entering stage ``first``, inside the program (`_train`).
    The defaults are the supervised step, whose program they leave as it
    was."""
    if mesh is not None:
        _mark("chip_train", cfg, x.shape, reconcile,
              tuple(mesh.shape.items()))

        def body(gp, gm, x, target, idx, lr_eff):
            c = gp.shape[1]
            gp2, gm2, err, fcnt, bcnt = _train(
                gp, gm, x.reshape(c, -1, x.shape[-1]),
                target.reshape(c, -1, target.shape[-1]), idx, cfg, lr_eff,
                reconcile, FARM_AXIS)
            return (gp2, gm2, err.reshape(-1, cfg.out_dim), fcnt, bcnt,
                    _stage_views(gp2, cfg), _stage_views(gm2, cfg))

        views = (_ROWS,) * cfg.S
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(_CHIPS, _CHIPS, _ROWS, _ROWS, _ALL, _ALL),
            out_specs=(_CHIPS, _CHIPS, _ROWS, _ALL, _ALL, views, views),
            check_vma=False)(gp, gm, x, target, idx, lr_eff)
    ae = target is None
    _mark("chip_train", cfg, x.shape, reconcile,
          *((first, ae) if first or ae else ()))
    serial = x.ndim == 2
    if serial:
        x, target = x[None], (target if ae else target[None])
    gp2, gm2, err, fcnt, bcnt = _train(gp, gm, x, target, idx, cfg, lr_eff,
                                       reconcile, first=first)
    return (gp2, gm2, (err[0] if serial else err), fcnt, bcnt,
            _stage_views(gp2, cfg), _stage_views(gm2, cfg))


@_returns_views(4)
@partial(jax.jit, static_argnames=("cfg", "reconcile", "first"),
         donate_argnums=(0, 1))
def chip_backward(gp, gm, acts, dps, delta, idx, cfg: ChipConfig,
                  lr_eff=0.1, reconcile: str | None = None, first: int = 0):
    """Compiled bwd + update phases over a stage slice (the pipeline
    fabric's per-chip entry point).  ``delta`` arrives padded to N_pad —
    (C, M, N_pad), or (M, N_pad) to rank-bridge the serial case like
    :func:`chip_infer`.  ``lr_eff`` is a traced scalar.  Returns the
    `Stepped` (gp', gm', upstream delta, counters): the upstream delta cut
    to the slice's fan-in, the views as in :func:`chip_train`.  ``first``
    (static) freezes the slice's stages below it, as in
    :func:`chip_train`; the delta is then the error entering stage
    ``first``, cut to its fan-in."""
    _mark("chip_backward", cfg, delta.shape, reconcile,
          *((first,) if first else ()))
    _dense_only(cfg, "chip_backward")
    serial = delta.ndim == 2
    if serial:
        acts, dps, delta = acts[:, None], dps[:, None], delta[None]
    gp2, gm2, dfin, cnt = _backward_scan(gp, gm, acts, dps, delta, idx,
                                         cfg, lr_eff, reconcile, first=first)
    dfin = dfin[..., :cfg.fan_in[first]]
    return (gp2, gm2, (dfin[0] if serial else dfin), cnt,
            _stage_views(gp2, cfg), _stage_views(gm2, cfg))


@partial(jax.jit, static_argnames=("cfg",))
def pad_wave(acts, dps, delta, cfg: ChipConfig):
    """A slice's wave as :func:`chip_forward` returned it, padded into the
    envelope :func:`chip_backward` takes, in one launch: (acts (S, M, L),
    dps (S, M, N_pad), delta (M, N_pad)).  Each stage's inputs fill lanes
    [1, fan_in] (bias slot 0 and the lanes beyond stay zero), its dot
    products lanes [0, fan_out), and the slice's output-side ``delta``
    (M, width) its first lanes."""
    _mark("pad_wave", cfg, delta.shape)
    M = delta.shape[0]
    acts_p = jnp.zeros((cfg.S, M, cfg.L), jnp.float32)
    dps_p = jnp.zeros((cfg.S, M, cfg.N_pad), jnp.float32)
    for s in range(cfg.S):
        acts_p = acts_p.at[s, :, 1:cfg.fan_in[s] + 1].set(acts[s])
        dps_p = dps_p.at[s, :, :cfg.fan_out[s]].set(dps[s])
    delta_p = jnp.zeros((M, cfg.N_pad), jnp.float32)
    return acts_p, dps_p, delta_p.at[:, :delta.shape[1]].set(delta)


# ---------------------------------------------------------------------------
# Serving session (farm front-end and pipeline front-end)
# ---------------------------------------------------------------------------

def _serve_wave(gp, gm, requests, idx, cfg: ChipConfig):
    """The recognition wave of :func:`serve_scan` on ``C`` lanes (C = the
    envelope's chip axis, 1 for serial stacks): request ``r`` runs on
    lane ``r % C``, each lane's requests as one batch of rows."""
    C = gp.shape[1] if gp.ndim == 5 else 1
    Qp, m, D = requests.shape
    lanes = jnp.moveaxis(requests.reshape(Qp // C, C, m, D), 1, 0)
    out, _ = _infer(gp, gm, lanes.reshape(C, Qp // C * m, D), idx, cfg)
    out = jnp.moveaxis(out.reshape(C, Qp // C, m, cfg.out_dim), 0, 1)
    return out.reshape(Qp, m, cfg.out_dim)


@partial(jax.jit, static_argnames=("cfg", "mesh"))
def serve_scan(gp, gm, requests, idx, cfg: ChipConfig, mesh=None):
    """A serving session as ONE stage-major recognition wave (DESIGN.md
    §8).

    ``gp``/``gm`` are the ordinary envelope, serial ``(S, T_max, rows,
    cols)`` or the farm's ``(S, C, T_max, rows, cols)``; ``requests`` is
    (Qp, m, fan_in) with Qp a multiple of the chip count C.  Request ``r``
    is served by chip ``r % C``: each chip's requests go through the
    ``S`` stages once, as one batch, in the wave of :func:`chip_infer`.
    Stages are sample-independent, so the answers are those of the beat
    wavefront the servers bill (request ``r`` entering at beat ``r //
    C``), which is replayed host-side and never run on the device.
    Returns the (Qp, m, out_dim) outputs in request order.

    With the farm's ``mesh`` each device runs the wave under
    ``shard_map`` on its own chips' lanes: the requests, viewed as
    (Qp / C, C, m, fan_in), split on the lane axis, and the outputs come
    back through the inverse view.
    """
    if mesh is None:
        _mark("serve_scan", cfg, requests.shape, gp.shape)
        return _serve_wave(gp, gm, requests, idx, cfg)
    _mark("serve_scan", cfg, requests.shape, gp.shape,
          tuple(mesh.shape.items()))
    C = gp.shape[1]
    Qp, m, D = requests.shape

    def body(gp, gm, lanes, idx):
        q_pad, c = lanes.shape[:2]
        out = _serve_wave(gp, gm, lanes.reshape(q_pad * c, m, D), idx, cfg)
        return out.reshape(q_pad, c, m, cfg.out_dim)

    lanes = P(None, FARM_AXIS)
    out = jax.shard_map(body, mesh=mesh,
                        in_specs=(_CHIPS, _CHIPS, lanes, _ALL),
                        out_specs=lanes, check_vma=False)(
        gp, gm, requests.reshape(Qp // C, C, m, D), idx)
    return out.reshape(Qp, m, cfg.out_dim)


def _microbatch(x) -> int:
    """Rows of request input ``x`` as the session slab takes it (a 1-D
    request is one row), read from its host-side shape."""
    shape = np.shape(x)
    return 1 if len(shape) < 2 else shape[0]


def check_fresh(server: str, built: int, now: int) -> None:
    """The stale-snapshot guard of both servers: a server serves the
    conductances of the version it was built at (``built``); once a
    train_step moved its owner to ``now`` it refuses to run."""
    if now != built:
        raise RuntimeError(
            f"conductances changed since this {server} was built (a "
            f"train_step ran); construct a fresh server — a server serves "
            f"the conductances it was built on")


@partial(jax.jit, static_argnames=("q_pad",))
def pack_requests(xs, q_pad: int):
    """A session's request inputs as the (q_pad, m, D) slab
    :func:`serve_scan` takes, in one launch: each input as rows (a 1-D
    request is one row), stacked in request order, and zero rows past the
    last request."""
    x = jnp.stack([jnp.atleast_2d(x) for x in xs]).astype(jnp.float32)
    return jnp.pad(x, ((0, q_pad - len(xs)), (0, 0), (0, 0)))


@partial(jax.jit, static_argnames=("q",))
def split_answers(out, q: int):
    """The first ``q`` requests' answers of a :func:`serve_scan` slab, in
    one launch: each request's (m, out_dim) rows, and all of them as one
    (q * m, out_dim) array."""
    return tuple(out[i] for i in range(q)), out[:q].reshape(-1, out.shape[-1])


def run_serve_session(queue, stacks, gp, gm, spec, n_lanes: int,
                      slot_m: int | None = None, mesh=None
                      ) -> tuple[int, int, int, int, jax.Array]:
    """Drain ``queue`` through :func:`serve_scan` (the shared front-end
    driver of `FarmServer` and `PipelineServer`): request ``r`` is served
    on lane ``r % n_lanes``, and the schedule bills it as entering at
    beat ``r // n_lanes``.  Every request must share one microbatch, and
    the server's earlier sessions' ``slot_m`` when given; both are checked
    from host shapes before anything is popped or launched.  The requests
    go to the device in one program (:func:`pack_requests`), run as one
    wave, and their answers come back in one (:func:`split_answers`).
    Completes every request in order and returns (requests, microbatch m,
    q_max, beats, the session's answers as one (requests * m, out_dim)
    array); the callers replay their own counter/link billing from the
    same schedule."""
    with spans.phase(spans.PREPARE):
        if not queue.pending:
            raise ValueError("a serving session needs at least one request")
        m = slot_m
        for r in queue.pending:
            rows = _microbatch(r.x)
            if m is None:
                m = rows
            elif rows != m:
                raise ValueError(
                    f"request {r.rid} has microbatch {rows}, session uses "
                    f"{m}; serve uniform request shapes")
        reqs = []
        while True:
            r = queue.pop()
            if r is None:
                break
            reqs.append(r)
        Q = len(reqs)
        q_max = -(-Q // n_lanes)
        # bucket the lane depth to a power of two so varying queue lengths
        # share compiled executables (the wave's shapes are static in
        # Qp).  The spare rows are zeros whose answers land only in rows
        # >= Q, all sliced away below; the schedule the callers bill is
        # the real one, so the returned q_max/beats are its own.
        q_pad = 1 << (q_max - 1).bit_length()
        x_arr = pack_requests(tuple(r.x for r in reqs), q_pad * n_lanes)
        idx, cfg = stacks.index_pytree(), chip_config(stacks, spec)
    with spans.phase(spans.DISPATCH):
        out = serve_scan(gp, gm, x_arr, idx, cfg, mesh=mesh)
    with spans.phase(spans.WRITEBACK):
        answers, flat = split_answers(out, Q)
        for r, a in zip(reqs, answers):
            queue.complete(r.rid, a)
    return Q, m, q_max, stacks.S - 1 + q_max, flat
