"""The part of collective_ms_per_step during which no other operation runs
on that device: communication that nothing hides."""
LAYER = "sharding"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or not any(
            d.collective_ns for d in run.trace.devices):
        return None
    return (run.trace.median(lambda d: d.collective_exposed_ns) * 1e-6
            / run.trace.steps)
