"""Peak device memory of the cell's compiled step, in GB (1e9 bytes) a
device: XLA's buffer assignment, argument + output + temp - alias, read by
the benchmark from the compiled executable. A property of the compiled
program, so it repeats exactly; the allocator's own peak does not see the
executable's temporaries on this libtpu (PERF.md section 7)."""


def read(run):
    return run.step_peak_bytes / 1e9
