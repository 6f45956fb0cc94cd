"""A `ChipPipeline` built for the network's greedy layer-wise pretraining:
the encoders and one temporary decoder per hidden layer, each pretraining
step's path split over ``modelled_chips`` modelled chips, driven at the
traffic's ``layer``."""
from __future__ import annotations

from bench import program as prog
from bench import reference_ae


def build(ctx) -> dict:
    from repro.sim.fabric import ChipPipeline
    cfg = ctx.cfg
    layer = int(ctx.traffic["layer"])
    decoders = reference_ae.initial_decoders(cfg, ctx.seed)
    pipe = ChipPipeline(prog.layer_dicts(ctx.g0), prog.crossbar_spec(cfg),
                        n_chips=int(cfg["modelled_chips"]),
                        rows=int(cfg["core_rows"]),
                        cols=int(cfg["core_cols"]), name=cfg["name"],
                        decoders=prog.layer_dicts(decoders))
    lr = float(ctx.traffic["lr"])

    def xval():
        rel = pipe.report().compare_hw()
        return {k: v for k, v in rel.items() if k.startswith("pretrain")}

    return {"step": lambda x: pipe.pretrain_step(layer, x, lr),
            "pair": lambda: prog.layer_pairs(
                [pipe.layers()[layer], pipe.decoders()[layer]]),
            "prefix": lambda: prog.layer_pairs(pipe.layers()[:layer]),
            "xval": xval}
