"""A `ChipFarm`: ``replicas`` data-parallel replicas of the modelled chip,
split over the cell's chips by a ``("chips",)`` mesh, as users train a
network faster than one chip trains it.  Each device holds its replicas'
conductances and runs their share of every step; the reconciliation is a
collective between the devices."""
from __future__ import annotations

from bench import program as prog


def build(ctx) -> dict:
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

    from repro.sim.cluster import ChipFarm
    cfg = ctx.cfg
    n = int(cfg["replicas"])
    mesh = Mesh(np.array(ctx.devices), ("chips",),
                axis_types=(AxisType.Auto,))
    farm = ChipFarm(prog.layer_dicts(ctx.g0), prog.crossbar_spec(cfg),
                    n_chips=n, rows=int(cfg["core_rows"]),
                    cols=int(cfg["core_cols"]), name=cfg["name"], mesh=mesh)
    if not farm._compiled_active():
        # the eager mesh path dispatches each stage under a fresh
        # shard_map, which compiles again inside every step
        raise RuntimeError("this farm's mesh path runs no compiled step: "
                           "its steps compile inside the window")
    lr, reconcile = float(ctx.traffic["lr"]), cfg["reconcile"]
    return {"step": lambda x, t: farm.train_step(x, t, lr,
                                                 reconcile=reconcile),
            "rows": NamedSharding(mesh, PartitionSpec("chips")),
            "layers": lambda: prog.layer_pairs(farm.layers()),
            "replicas": lambda: [prog.layer_pairs(
                farm.extract_chip(c).layers()) for c in range(n)],
            "xval": lambda: farm.report().compare_hw()}
