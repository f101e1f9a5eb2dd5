"""Device milliseconds a step the expert layers take beside their routed
products: the scopes `moe_route` (scores, top-k, gates), `moe_dispatch`
(sort, gather), `moe_combine` (weighted sum back to tokens) and
`shared_expert` (the expert every token passes), forward, backward and
recomputed (`benchmark/part_scopes.py`). With `relu2_experts_ms_per_step`
(the `moe_experts` scope) it is the expert layers whole, less the fusions
that mix two of the five. Absent where the run is untraced or the program
has no such scopes."""
from benchmark import part_scopes

LAYER = "experts"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
PARTS = ("moe_route", "moe_dispatch", "moe_combine", "shared_expert")


def read(run):
    return part_scopes.part_ms_per_step(run, PARTS)
