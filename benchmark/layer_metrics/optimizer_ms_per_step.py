"""Device milliseconds a step in instructions whose role is `optimizer`:
unscale and finite check, clip, regularisation, the update, master-weight
casts. Self time on the ops line, a fusion counting where every
role-carrying instruction of its body is `optimizer` (`benchmark/roles.py`).
Median over the devices. Absent where the run is untraced or the program
has no role scopes."""
from benchmark import roles

LAYER = "entry"
MOVES = "step_ms"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    return roles.role_ms_per_step(run, "optimizer")
