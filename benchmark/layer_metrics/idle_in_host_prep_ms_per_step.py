"""Milliseconds a step in which no operation ran on the first device while
the loop was inside any `Executor::*` or `DataLoader::*` span other than
`Executor::dispatch`: the Python before and after the call, and the wait
for a batch. With `idle_in_dispatch_ms_per_step` it is what the program,
and not the benchmark's own loop, holds of the device's idle time
(`benchmark/host_spans.py`). Absent where the run is untraced or the
program opens no such span."""
from benchmark import host_spans

LAYER = "device"
MOVES = "step_ms"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    return host_spans.idle_ms_per_step(
        run, host_spans.EXECUTOR, host_spans.LOADER,
        but=(host_spans.DISPATCH,))
