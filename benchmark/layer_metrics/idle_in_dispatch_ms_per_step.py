"""Milliseconds a step in which no operation ran on the first device while
the loop was inside `Executor::dispatch`: the gaps of the device's ops line
in the traced window, each given to the innermost host span that overlaps
it (`benchmark/host_spans.py`). The device waiting for the copy of a host
feed, or for the enqueue itself. Absent where the run is untraced or the
program opens no such span; 0 where the device never idled under one."""
from benchmark import host_spans

LAYER = "device"
MOVES = "step_ms"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    return host_spans.idle_ms_per_step(run, host_spans.DISPATCH)
