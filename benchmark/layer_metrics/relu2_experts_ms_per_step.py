"""Device milliseconds a step under the program's `moe_experts` scope in a
configuration whose routed experts are two matrices and a squared ReLU:
the two grouped products of every expert layer and the activation between
them, forward, backward and recomputed (`benchmark/part_scopes.py`).
Absent where the run is untraced or the program has no such scope."""
from benchmark import part_scopes

LAYER = "experts"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
PARTS = ("moe_experts",)


def read(run):
    return part_scopes.part_ms_per_step(run, PARTS)
