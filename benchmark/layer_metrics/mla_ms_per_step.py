"""Device milliseconds a step under the program's `mla` scope: the whole
latent-attention sublayer (low-rank projections, latent norms, rotation,
the flash calls and W_o), forward and backward
(`benchmark/part_scopes.py`). Absent where the run is untraced or the
program has no such scope."""
from benchmark import part_scopes

LAYER = "attention"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
PARTS = ("mla",)


def read(run):
    return part_scopes.part_ms_per_step(run, PARTS)
