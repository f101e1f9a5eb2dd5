"""Device milliseconds a step that no single role takes: busy time less
`forward_ms_per_step`, `backward_ms_per_step` and `optimizer_ms_per_step`
on each device, which is the self time of `mixed` fusions (bodies that
carry more than one role) plus that of instructions without a role
(`benchmark/roles.py`; `python3 -m benchmark.roles <trace dir> <steps>`
lists both). The check on the other three. Median over the devices. Absent
where the run is untraced or the program has no role scopes."""
from benchmark import roles

LAYER = "device"
MOVES = "step_ms"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    return roles.unattributed_ms_per_step(run)
