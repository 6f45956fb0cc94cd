"""Roofline share of the fused training kernel behind
`ops.crossbar_train_stacked` in a pretraining cell: the least time the
chips could take for the window's training work on the pair, encoder
``layer`` and its temporary decoder (`work_pretrain.train_work`, on the
real cores and the batch, never on the padded stacks, its bytes those
the kernel moves to and from HBM: `work_pretrain.hbm_shares`), over the
kernel's device time in the trace."""
from __future__ import annotations


def read(r):
    from bench import trace as tr
    from bench import work_pretrain
    if not r.calls:
        return None
    ns = sum(tr.kernel_ns(d, "train") for d in r.red.devices)
    shares = work_pretrain.hbm_shares(r.red.devices, "train")
    if not ns or shares is None:
        return None
    m = r.samples / r.calls
    work = work_pretrain.train_work(r.cfg, int(r.traffic["layer"]), m,
                                    shares) * r.calls
    least, _ = work.least_seconds(r.peak)
    return 100.0 * least * len(r.red.devices) / (ns * 1e-9) / r.chips
