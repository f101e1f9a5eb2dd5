"""Device milliseconds a step in instructions whose role is `backward`: the
transposes, the recomputation under `remat_ffn` (emitted at the grad op, so
under the `backward` scope) and the gradient collectives the partitioner
attaches to them. Self time on the ops line, a fusion counting where every
role-carrying instruction of its body is `backward` (`benchmark/roles.py`).
Median over the devices. Absent where the run is untraced or the program
has no role scopes."""
from benchmark import roles

LAYER = "entry"
MOVES = "step_ms"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    return roles.role_ms_per_step(run, "backward")
