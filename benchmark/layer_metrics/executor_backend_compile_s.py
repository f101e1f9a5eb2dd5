"""Seconds this process spent in XLA's compile of the executor's programs,
or in the read of their executables from JAX's persistent cache:
`executor_backend_compile_seconds_total` (`paddle_tpu/fluid/monitor.py`,
from `jax.monitoring`, counted only inside the executor's compile spans),
the process's total when the reader runs. Absent where the program does
not count it."""
from benchmark import host_spans

LAYER = "step"
MOVES = "setup_s"
UNIT = "s"
SOURCE = "host_clock"


def read(run):
    return host_spans.program_counter_seconds(
        "executor_backend_compile_seconds_total")
