"""Median host milliseconds the loop waits in the loader's next(): what the
input layer costs a step. Near 0 while the loader's thread keeps ahead."""
LAYER = "input"
MOVES = "step_ms"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    wait = run.per_step("data_wait_s")
    return None if wait is None else wait * 1e3
