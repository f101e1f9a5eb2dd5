"""Host milliseconds a step inside `Executor::dispatch`, the call of the
compiled step: flattening the arguments, the copy of a host feed to the
device, the enqueue. The span's self time on the loop's line of the traced
window, summed over the window and divided by its steps
(`benchmark/host_spans.py`). Absent where the run is untraced or the
program opens no such span."""
from benchmark import host_spans

LAYER = "step"
MOVES = "step_ms"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    return host_spans.self_ms_per_step(run, host_spans.DISPATCH)
