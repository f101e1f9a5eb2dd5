"""Host seconds to build the cell's program: build_*_program,
mixed_precision.decorate, fleet where the cell has a mesh, minimize. Pure
host work, paid by every process."""
LAYER = "entry"
MOVES = "setup_s"
UNIT = "s"
SOURCE = "host_clock"


def read(run):
    return run.setup["program_build_s"]
