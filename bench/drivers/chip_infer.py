"""One `VirtualChip`: the network placed on one modelled chip, recognizing
batches of new input."""
from __future__ import annotations

from bench import program as prog


def build(ctx) -> dict:
    from repro.core import hw_model as hw
    from repro.sim.chip import VirtualChip
    cfg = ctx.cfg
    rows, cols = int(cfg["core_rows"]), int(cfg["core_cols"])
    chip = VirtualChip(prog.layer_dicts(ctx.g0), prog.crossbar_spec(cfg),
                       rows=rows, cols=cols, name=cfg["name"])
    cost = hw.network_cost(cfg["name"], list(cfg["dims"]), rows=rows,
                           cols=cols)

    def xval():
        # the report compares both phases; only recognition has run
        rel = chip.report().compare_hw(cost)
        return {k: v for k, v in rel.items() if k.startswith("infer")}

    return {"infer": chip.infer, "xval": xval}
