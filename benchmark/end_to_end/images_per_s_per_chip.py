"""Images a second a chip: images in a step over the median untraced step
time, over the chips."""


def read(run):
    return run.units_per_s_per_chip()
