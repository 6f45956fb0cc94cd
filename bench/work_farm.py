"""Operations and bytes of the farm's local outer-product kernel
(`ops.crossbar_dw_stacked`), from the configuration alone.

Counted as `bench/work.py` counts: on the placement's real cores and the
real rows, never on the padded envelope.  Each replica's real cores take
its own rows: a core of ``rows`` x ``cols`` crossbars reads its inputs
and errors once, and writes its outer product once.  Over ``replicas``
replicas and ``m`` rows in all, that is ``2 m rows cols`` operations a
core, and float32 ``m rows + m cols`` read and ``replicas rows cols``
written a core.
"""
from __future__ import annotations

from bench import work


def dw_work(cfg: dict, m: float, replicas: int) -> work.Work:
    """The local outer products of one farm step of ``m`` rows in all."""
    R, C = cfg["core_rows"], cfg["core_cols"]
    n = sum(work.real_cores(cfg))
    return work.Work(flops=n * 2.0 * m * R * C,
                     bytes=n * work.F32_BYTES * (m * R + m * C
                                                 + replicas * R * C))
