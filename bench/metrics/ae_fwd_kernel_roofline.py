"""Roofline share of the forward kernel behind `ops.crossbar_fwd_stacked`
in a pretraining cell: the least time the chips could take for the
window's forward waves through the frozen prefix and the pair
(`work_pretrain.fwd_work`, on the real cores, the conductances read once
per call, its bytes those the kernel moves to and from HBM:
`work_pretrain.hbm_shares`), over the kernel's device time in the
trace."""
from __future__ import annotations


def read(r):
    from bench import trace as tr
    from bench import work_pretrain
    if not r.calls:
        return None
    ns = sum(tr.kernel_ns(d, "fwd") for d in r.red.devices)
    shares = work_pretrain.hbm_shares(r.red.devices, "fwd")
    if not ns or shares is None:
        return None
    m = r.samples / r.calls
    work = work_pretrain.fwd_work(r.cfg, int(r.traffic["layer"]), m,
                                  shares) * r.calls
    least, _ = work.least_seconds(r.peak)
    return 100.0 * least * len(r.red.devices) / (ns * 1e-9) / r.chips
