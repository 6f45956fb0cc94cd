"""Operations and bytes of one greedy pretraining step of hidden layer
``layer``, from the configuration, counted as `bench/work.py` counts: on
the real cores of the step's path, never on the padded stacks.

The path is the frozen encoders ``0 .. layer-1``, encoder ``layer`` and
its temporary decoder, so its widths are ``dims[:layer + 2] +
[dims[layer]]``: every one of its layers takes the forward wave, and the
fused training kernel runs on the last two, the pair.

`bench/work.py` counts every operand and result of a kernel as HBM
traffic.  On a TPU, XLA may place them in the core's on-chip memory
(memory space 1, ``S(1)`` in a layout) before the kernel runs: the
pretraining path's programs do so with the whole conductance stacks, so
the kernel reads and writes them on-chip and its time holds none of their
HBM traffic.  The bytes here therefore count each operand and result of a
kernel only in the share of the kernel's executions in the trace in which
its layout lies in HBM (`hbm_shares`); the kernel's time is its own
(`trace.kernel_ns`), as in the other cells.
"""
from __future__ import annotations

import re

from bench import trace as tr
from bench import work

ON_CHIP = "S(1)"
ARRAY = re.compile(r"f32\[[\d,]*\](\{[^}]*\})?")

#: what each operand and then each result of a kernel is in its work, in
#: the order of its HLO text: ``g`` one crossbar's conductances (rows x
#: cols), ``x`` a core's input rows (m x rows), ``d`` a core's error or
#: output rows (m x cols), ``None`` a result `bench/work.py` does not
#: count.  train: (g+, g-, x, d) -> (y, dx, g+', g-'); fwd: (x, g+, g-)
#: -> y.
PARTS = {"train": ("g", "g", "x", "d", None, "x", "g", "g"),
         "fwd": ("x", "g", "g", "d")}


def path_dims(dims, layer: int) -> list[int]:
    """The widths of hidden layer ``layer``'s pretraining path."""
    return list(dims[:layer + 2]) + [dims[layer]]


def in_hbm(op_name: str) -> list[bool]:
    """Whether each operand and then each result of a kernel's HLO text
    lies in HBM (its layout names no on-chip memory space)."""
    head, _, rest = op_name.partition("custom-call(")
    operands = rest.split("custom_call_target")[0]
    results = head.split(" = ", 1)[-1]
    return [ON_CHIP not in (m.group(1) or "")
            for part in (operands, results) for m in ARRAY.finditer(part)]


def hbm_shares(devices, kind: str) -> list[float] | None:
    """For each operand and result of ``kind``'s kernels (`PARTS`), the
    share of their executions in the trace in which it lies in HBM; None
    where no such kernel ran."""
    flags = [in_hbm(o.name) for d in devices for o in d.ops
             if tr.kernel_kind(o.name) == kind]
    if not flags:
        return None
    return [sum(col) / len(flags) for col in zip(*flags)]


def _bytes(kind: str, cfg: dict, m: float, shares) -> float:
    """HBM bytes of one real core's ``kind`` kernel at ``m`` rows."""
    R, C = cfg["core_rows"], cfg["core_cols"]
    size = {"g": R * C, "x": m * R, "d": m * C, None: 0.0}
    parts = PARTS[kind]
    shares = [1.0] * len(parts) if shares is None else shares
    return work.F32_BYTES * sum(size[p] * s for p, s in zip(parts, shares))


def fwd_work(cfg: dict, layer: int, m: float, shares=None) -> work.Work:
    """The forward wave of ``m`` rows through every real core of the
    path, the frozen prefix and the pair: `work.fwd_work`, with the bytes
    of each operand and result in the ``shares`` of the executions in
    which it lies in HBM (all of them by default)."""
    n = sum(work.real_cores(dict(cfg, dims=path_dims(cfg["dims"], layer))))
    R, C = cfg["core_rows"], cfg["core_cols"]
    return work.Work(n * 2.0 * m * R * C, n * _bytes("fwd", cfg, m, shares))


def train_work(cfg: dict, layer: int, m: float, shares=None) -> work.Work:
    """The fused training kernel over the real cores of the pair (encoder
    ``layer`` and its decoder) for a batch of ``m`` rows:
    `work.train_work`, with the bytes counted as in `fwd_work`."""
    d = cfg["dims"]
    n = sum(work.real_cores(dict(cfg, dims=[d[layer], d[layer + 1],
                                            d[layer]])))
    R, C = cfg["core_rows"], cfg["core_cols"]
    return work.Work(n * 4.0 * m * R * C,
                     n * _bytes("train", cfg, m, shares))


def passes(dims, layer: int) -> float:
    """Network operations of one step per sample, over those of one
    recognition pass of ``dims`` (`work.network_ops`): the frozen
    encoders' forward, and forward, backward and update on the pair."""
    pairs = [a * b for a, b in zip(dims, dims[1:])]
    return (sum(pairs[:layer]) + 3 * 2 * pairs[layer]) / sum(pairs)
