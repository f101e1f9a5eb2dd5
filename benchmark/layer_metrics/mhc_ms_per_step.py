"""Device milliseconds a step in the residual path of the hyper-connections:
the three mappings with Sinkhorn (scope `mhc_map`) and the mixing of the
streams (`mhc_mix`: H_pre X, H_res X + H_post^T y, the readout), forward,
backward and recomputed (`benchmark/part_scopes.py`). Absent where the run
is untraced or the program has no such scope."""
from benchmark import part_scopes

LAYER = "residual"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
PARTS = ("mhc_map", "mhc_mix")


def read(run):
    return part_scopes.part_ms_per_step(run, PARTS)
