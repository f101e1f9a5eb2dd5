"""Device milliseconds a step in which a collective is in flight (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all; on the ops line
or the async lines), median over the devices. Absent where the trace
holds no collective."""
LAYER = "sharding"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or not any(
            d.collective_ns for d in run.trace.devices):
        return None
    return (run.trace.median(lambda d: d.collective_ns) * 1e-6
            / run.trace.steps)
