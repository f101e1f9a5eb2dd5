"""Device milliseconds a step in instructions whose role is `forward`: self
time on the ops line of the instructions lowered under the program's
`forward` scope (`paddle_tpu/ops/registry.py:emit_ops`), a fusion counting
where every role-carrying instruction of its body is `forward`
(`benchmark/roles.py`). Median over the devices. Absent where the run is
untraced or the program has no role scopes."""
from benchmark import roles

LAYER = "entry"
MOVES = "step_ms"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    return roles.role_ms_per_step(run, "forward")
