"""Device milliseconds a step under the program's `moe_experts` scope in a
configuration that has a shared expert beside the routed ones: the three
grouped products of every expert layer and the SwiGLU activation between
them, forward, backward and recomputed (`benchmark/part_scopes.py`; what
`moe_experts_ms_per_step` reads in the LFM2 cell, split among this
family's part names). Absent where the run is untraced or the program has
no such scope."""
from benchmark import part_scopes

LAYER = "experts"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
PARTS = ("moe_experts",)


def read(run):
    return part_scopes.part_ms_per_step(run, PARTS)
