"""Device milliseconds a step under the program's `moe_experts` scope: the
three grouped products of every expert layer and the SwiGLU activation
between them, forward, backward and recomputed (`benchmark/scopes.py`).
Absent where the run is untraced or the program has no such scope."""
from benchmark import scopes

LAYER = "experts"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
PARTS = ("moe_experts",)


def read(run):
    return scopes.part_ms_per_step(run, PARTS)
