"""Device milliseconds a step under the program's `shared_expert` scope:
the SwiGLU expert every token passes beside the routed ones, forward,
backward and recomputed (`benchmark/part_scopes.py`). Absent where the run
is untraced or the program has no such scope."""
from benchmark import part_scopes

LAYER = "experts"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
PARTS = ("shared_expert",)


def read(run):
    return part_scopes.part_ms_per_step(run, PARTS)
