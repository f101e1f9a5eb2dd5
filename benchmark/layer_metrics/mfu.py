"""Model FLOP/s utilization, per cent: the family's model-FLOP formula
(forward + backward, recomputation not counted) over the median untraced
step time, the chips and the table peak of benchmark/peaks.json."""
LAYER = "device"
MOVES = "step_ms"
UNIT = "%"
SOURCE = "host_clock"


def read(run):
    step_s = run.step_seconds()
    if step_s is None or run.peaks is None:
        return None
    flops = run.cell.family.step_flops(
        run.cell.config, run.cell.traffic, int(run.cell.traffic["batch"]))
    return (100.0 * flops / step_s / run.chips
            / run.peaks["bf16_flops_per_s"])
