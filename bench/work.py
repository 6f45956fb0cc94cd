"""Operations and bytes of the work the cells ask for, from the
configuration alone.

Kernel work is counted on the placement's real cores, never on the padded
envelope a kernel is handed: a layer of ``fan_in`` inputs (plus the bias
line) and ``fan_out`` neurons occupies ``ceil((fan_in + 1) / rows)`` by
``ceil(fan_out / cols)`` cores of ``rows`` x ``cols`` crossbars (Fig. 14's
aggregation cores add outside the kernels).  Each real core is a whole
crossbar: the modelled hardware evaluates all of it.  Operands are float32,
as the configuration states.  So the count stays the same whatever
implements it, and dropping padding reads as a gain.

Network operations per sample follow Table I's widths: a recognition pass
is ``2 * sum(d_i * d_(i+1))``, a training step three times that (forward,
error transpose-product, outer product).
"""
from __future__ import annotations

import json
import math
import os
from typing import NamedTuple

F32_BYTES = 4
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peak(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a kind missing
    from ``peaks.json`` is an error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}")
    return table[device_kind]


class Work(NamedTuple):
    flops: float
    bytes: float

    def __mul__(self, k):
        return Work(self.flops * k, self.bytes * k)

    def least_seconds(self, peak: dict) -> tuple[float, str]:
        """The least time a chip with ``peak`` could take, and which of
        compute and bandwidth bounds it."""
        t_c = self.flops / peak["flops_per_s"]
        t_b = self.bytes / peak["hbm_bytes_per_s"]
        return (t_c, "compute") if t_c >= t_b else (t_b, "bandwidth")


def real_cores(cfg: dict) -> list[int]:
    """Main-grid cores of each layer."""
    rows, cols = cfg["core_rows"], cfg["core_cols"]
    dims = cfg["dims"]
    return [math.ceil((f + 1) / rows) * math.ceil(o / cols)
            for f, o in zip(dims, dims[1:])]


def fwd_work(cfg: dict, m: float) -> Work:
    """One recognition wave of ``m`` rows over every real core: the
    contraction, both conductances of each pair read once, inputs read
    and outputs written."""
    R, C = cfg["core_rows"], cfg["core_cols"]
    n = sum(real_cores(cfg))
    return Work(flops=n * 2.0 * m * R * C,
                bytes=n * F32_BYTES * (2.0 * R * C + m * R + m * C))


def train_work(cfg: dict, m: float) -> Work:
    """The fused training kernel over every real core for a batch of
    ``m`` rows: the error transpose-product and the outer product; both
    conductances read and written back, inputs and errors read, upstream
    errors written."""
    R, C = cfg["core_rows"], cfg["core_cols"]
    n = sum(real_cores(cfg))
    return Work(flops=n * 4.0 * m * R * C,
                bytes=n * F32_BYTES * (4.0 * R * C + 2.0 * m * R + m * C))


def network_ops(dims, *, passes: int) -> int:
    """Network operations per sample (Table I widths): ``passes`` times a
    recognition pass (training makes three: forward, backward, update)."""
    return passes * 2 * sum(a * b for a, b in zip(dims, dims[1:]))
