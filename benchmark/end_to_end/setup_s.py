"""Top of run.py, before JAX is imported, to the start of the window."""


def read(run):
    return run.setup["setup_s"]
