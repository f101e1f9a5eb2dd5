"""Host milliseconds a step the loop spends taking a batch from the loader:
`DataLoader::next` (the wait on the prefetch queue) with the
`DataLoader::materialize` inside it, self times on the loop's line of the
traced window, summed and divided by its steps (`benchmark/host_spans.py`).
The producer thread's spans are on another line and are not counted: what
it costs shows here only as a wait. Absent where the run is untraced or
the program opens no such span."""
from benchmark import host_spans

LAYER = "input"
MOVES = "step_ms"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    return host_spans.self_ms_per_step(run, host_spans.LOADER)
