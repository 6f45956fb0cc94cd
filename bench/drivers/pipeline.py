"""A `ChipPipeline`: the network split over ``modelled_chips`` modelled
chips (`core.mapping.split_network`), as users run a network larger than
one chip."""
from __future__ import annotations

from bench import program as prog


def build(ctx) -> dict:
    from repro.sim.fabric import ChipPipeline
    cfg = ctx.cfg
    pipe = ChipPipeline(prog.layer_dicts(ctx.g0), prog.crossbar_spec(cfg),
                        n_chips=int(cfg["modelled_chips"]),
                        rows=int(cfg["core_rows"]),
                        cols=int(cfg["core_cols"]), name=cfg["name"])
    lr = float(ctx.traffic.get("lr", 0.0))
    return {"step": lambda x, t: pipe.train_step(x, t, lr),
            "serve": lambda x: pipe.serve(x)[0],
            "layers": lambda: prog.layer_pairs(pipe.layers()),
            "xval": lambda: pipe.report().compare_hw()}
