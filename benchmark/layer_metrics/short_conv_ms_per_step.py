"""Device milliseconds a step under the program's `short_conv` scope: the
gated short-convolution operators, their two projections included, forward
and backward (`benchmark/scopes.py`). Absent where the run is untraced or
the program has no such scope."""
from benchmark import scopes

LAYER = "short_conv"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
PARTS = ("short_conv",)


def read(run):
    return scopes.part_ms_per_step(run, PARTS)
