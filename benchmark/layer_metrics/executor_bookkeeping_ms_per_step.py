"""Host milliseconds a step of Python around the compiled step's call:
`Executor::run`'s own time and every `Executor::*` span inside it other
than `feed` and `dispatch` (`lookup`: the cache key and the look-up; `state`:
the scope's several hundred arrays by name; `commit`: the new state into
the scope; `fetch`). Self times on the loop's line of the traced window,
summed and divided by its steps (`benchmark/host_spans.py`). Absent where
the run is untraced or the program opens no such span."""
from benchmark import host_spans

LAYER = "step"
MOVES = "step_ms"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    return host_spans.self_ms_per_step(
        run, host_spans.EXECUTOR, but=(host_spans.FEED, host_spans.DISPATCH))
