"""Device milliseconds a step under the program's `moe_experts` scope in a
`glm4_moe_lite` configuration: the three grouped products of every expert
layer, the multi-token-prediction module's among them, and the SwiGLU
activation between them, forward, backward and recomputed
(`benchmark/part_scopes.py`; what `routed_experts_ms_per_step` reads in the
Xing4 cell). Absent where the run is untraced or the program has no such
scope."""
from benchmark import part_scopes

LAYER = "experts"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
PARTS = ("moe_experts",)


def read(run):
    return part_scopes.part_ms_per_step(run, PARTS)
