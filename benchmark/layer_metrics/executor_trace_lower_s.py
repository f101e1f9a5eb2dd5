"""Seconds this process spent turning the executor's programs into jaxprs
and those into StableHLO modules: `executor_trace_seconds_total` +
`executor_lower_seconds_total` (`paddle_tpu/fluid/monitor.py`, from
`jax.monitoring`, counted only inside the executor's compile spans), the
process's total when the reader runs; the window compiles nothing. Every
process pays it, in front of JAX's compile cache. Absent where the program
does not count it."""
from benchmark import host_spans

LAYER = "step"
MOVES = "setup_s"
UNIT = "s"
SOURCE = "host_clock"


def read(run):
    return host_spans.program_counter_seconds(
        "executor_trace_seconds_total", "executor_lower_seconds_total")
