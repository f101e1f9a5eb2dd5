"""Median host milliseconds inside one Executor.run call (feed preparation,
host-to-device transfer, dispatch). It sets the pace only where it nears
the device's step time."""
LAYER = "step"
MOVES = "step_ms"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    call = run.per_step("run_call_s")
    return None if call is None else call * 1e3
