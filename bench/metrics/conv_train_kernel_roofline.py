"""Roofline share of the conv stages' fused training kernels (behind
`ops.crossbar_train_stacked`, taking a conv stage's positions folded
into its rows): the least time the chips could take for the window's
conv training work, counted as LeNet-5's real work
(`work_conv.train_work`: the outer product over rows and positions, and
the error handed down above the first layer, the bytes those the
kernels move to and from HBM), over those kernels' device time in the
trace."""
from __future__ import annotations


def read(r):
    from bench import work_conv
    if not r.calls or "conv" not in r.cfg:
        return None
    m = r.samples // r.calls
    ops = work_conv.conv_kernels(r.red.devices, "train", r.cfg, m)
    ns = sum(o.dur for o in ops)
    if not ns:
        return None
    shares = work_conv.hbm_shares(ops, "train")
    work = work_conv.train_work(r.cfg, m, shares) * r.calls
    least, _ = work.least_seconds(r.peak)
    return 100.0 * least * len(r.red.devices) / (ns * 1e-9) / r.chips
