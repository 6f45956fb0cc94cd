"""The bridge from a configuration file to the program's own types: the
crossbar constraints it states, and the benchmark's conductances as the
per-layer dicts the program's objects take."""
from __future__ import annotations


def crossbar_spec(cfg: dict):
    """The program's `CrossbarSpec` for the constraints ``cfg`` states."""
    from repro.core.crossbar import CrossbarSpec
    return CrossbarSpec(
        rows=int(cfg["core_rows"]), cols=int(cfg["core_cols"]),
        w_max=float(cfg["w_max"]), adc_bits=int(cfg["adc_bits"]),
        err_bits=int(cfg["err_bits"]),
        update_levels=int(cfg["update_levels"]),
        max_update=float(cfg["max_update"]),
        transport_quant=bool(cfg["transport_quant"]),
        error_quant=bool(cfg["error_quant"]),
        update_quant=bool(cfg["update_quant"]))


def layer_dicts(layers) -> list[dict]:
    """(g_plus, g_minus) pairs as the program's per-layer dicts."""
    return [{"g_plus": gp, "g_minus": gm} for gp, gm in layers]


def layer_pairs(layers) -> list[tuple]:
    """The program's per-layer dicts as (g_plus, g_minus) pairs."""
    return [(p["g_plus"], p["g_minus"]) for p in layers]
