"""Distribution substrate: logical sharding rules, compressed collectives,
and pipeline parallelism.

sharding.py     ParamSpec trees, logical->physical rules, activation
                constraints (no-ops off-mesh)
collectives.py  bf16/int8-compressed gradient all-reduce + pure-DP step
pipeline.py     GPipe microbatch ring over a mesh axis
"""
