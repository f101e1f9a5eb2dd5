"""Median step time: a group's seconds over its steps, median over the
groups of the window. The one end-to-end time every cell has, so that a
per-layer metric of every cell has a metric to move."""


def read(run):
    step_s = run.step_seconds()
    return None if step_s is None else step_s * 1e3
