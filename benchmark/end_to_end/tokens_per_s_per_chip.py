"""Tokens a second a chip: tokens in a step (padding included, the step
computes them) over the median untraced step time, over the chips."""


def read(run):
    return run.units_per_s_per_chip()
