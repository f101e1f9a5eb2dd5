"""Host milliseconds a step inside `Executor::feed` (`_prepare_feed`: the
feed's values to arrays of the program's dtypes): the span's self time on
the loop's line of the traced window, summed over the window and divided by
its steps, so that the three `executor_*_ms_per_step` add up to the time
inside `Executor::run` (`benchmark/host_spans.py`). Absent where the run is
untraced or the program opens no such span."""
from benchmark import host_spans

LAYER = "step"
MOVES = "step_ms"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    return host_spans.self_ms_per_step(run, host_spans.FEED)
