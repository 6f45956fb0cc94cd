"""One `VirtualChip`: the network placed on one modelled chip, trained."""
from __future__ import annotations

from bench import program as prog


def build(ctx) -> dict:
    from repro.core import hw_model as hw
    from repro.sim.chip import VirtualChip
    cfg = ctx.cfg
    rows, cols = int(cfg["core_rows"]), int(cfg["core_cols"])
    chip = VirtualChip(prog.layer_dicts(ctx.g0), prog.crossbar_spec(cfg),
                       rows=rows, cols=cols, name=cfg["name"])
    lr = float(ctx.traffic["lr"])
    cost = hw.network_cost(cfg["name"], list(cfg["dims"]), rows=rows,
                           cols=cols)

    def xval():
        # the report compares both phases; only training has run
        rel = chip.report().compare_hw(cost)
        return {k: v for k, v in rel.items() if k.startswith("train")}

    return {"step": lambda x, t: chip.train_step(x, t, lr),
            "layers": lambda: prog.layer_pairs(chip.layers()),
            "xval": xval}
