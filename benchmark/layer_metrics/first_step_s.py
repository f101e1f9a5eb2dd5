"""Host seconds of the first step of the cell's program, to the fetch of its
loss: tracing and lowering the Program, the compile or the read from the
compile cache, and one step."""
LAYER = "step"
MOVES = "setup_s"
UNIT = "s"
SOURCE = "host_clock"


def read(run):
    return run.setup["first_step_s"]
