"""Device milliseconds a step under the scopes `moe_route` (scores, top-k,
gates), `moe_dispatch` (sort, gather) and `moe_combine` (weighted sum back
to tokens): what the sparsity costs beside its products
(`benchmark/scopes.py`). Absent where the run is untraced or the program
has no such scopes."""
from benchmark import scopes

LAYER = "experts"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
PARTS = ("moe_route", "moe_dispatch", "moe_combine")


def read(run):
    return scopes.part_ms_per_step(run, PARTS)
