"""One `VirtualChip` holding a conv network: the configuration's conv
layers (``conv``, their kernels from `bench/reference_conv.py`) and the
dense chain ``dims`` after them, on one modelled chip, trained."""
from __future__ import annotations

from bench import program as prog
from bench import reference_conv


def build(ctx) -> dict:
    from repro.core import hw_model as hw
    from repro.core.mapping import ConvShape
    from repro.sim.chip import VirtualChip
    cfg = ctx.cfg
    rows, cols = int(cfg["core_rows"]), int(cfg["core_cols"])
    shapes = [ConvShape.from_config(c) for c in cfg["conv"]]
    kernels = reference_conv.initial_conv_layers(cfg, ctx.seed)
    chip = VirtualChip(prog.layer_dicts(kernels) + prog.layer_dicts(ctx.g0),
                       prog.crossbar_spec(cfg), rows=rows, cols=cols,
                       name=cfg["name"], conv=shapes)
    lr = float(ctx.traffic["lr"])
    cost = hw.conv_network_cost(cfg["name"], shapes, list(cfg["dims"]),
                                rows=rows, cols=cols)

    def xval():
        # training's time, energy and IO, and each conv layer's counts
        rel = chip.report().compare_hw(cost)
        return {k: v for k, v in rel.items()
                if k.startswith(("train", "conv"))}

    return {"step": lambda x, t: chip.train_step(x, t, lr),
            "layers": lambda: prog.layer_pairs(chip.layers()),
            "xval": xval}
