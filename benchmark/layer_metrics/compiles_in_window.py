"""executor_cache_misses_total as it moved inside the window: compiles of the
program's own cache. The window is warm, so this is 0; more fails correct."""
LAYER = "step"
MOVES = "step_ms"
UNIT = "count"
SOURCE = "program_counter"


def read(run):
    return run.counters["executor_cache_misses_total"]
