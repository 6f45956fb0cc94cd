"""The whole step's share of the chips' peak: network operations per
sample (Table I widths, `work.network_ops`: the loop's passes over the
network per sample, three for training) times the samples completed in the traced window, over
the window's seconds, the chips and one chip's peak (`peaks.json`)."""
from __future__ import annotations


def read(r):
    if not r.samples:
        return None
    ops = r.work.network_ops(r.cfg["dims"], passes=r.passes)
    rate = ops * r.samples / r.red.window_s
    return 100.0 * rate / (r.chips * r.peak["flops_per_s"])
