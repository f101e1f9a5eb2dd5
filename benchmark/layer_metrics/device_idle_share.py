"""Share of the traced window in which no operation ran on the device, per
cent, median over the devices: 1 - union of the ops line / window."""
LAYER = "device"
MOVES = "step_ms"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.median(lambda d: d.idle_share)
