"""Share of their roofline the routed experts' grouped products reach in a
`glm4_moe_lite` configuration, per cent: the least time the chip could
take for them (`kernels/lite_experts.py`: three passes of three products
over the expected rows in every expert layer, the module's among them, the
larger of operations over the bf16 peak and bytes over the HBM peak) over
the device time under the `moe_experts` scope."""
from benchmark import manifest, part_scopes

LAYER = "experts"
MOVES = "tokens_per_s_per_chip"
UNIT = "%"
SOURCE = "device_trace"
PARTS = ("moe_experts",)


def read(run):
    took_ms = part_scopes.part_ms_per_step(run, PARTS)
    if not took_ms or run.peaks is None:
        return None
    flops, nbytes = manifest.load_module("kernels", "lite_experts").step_work(
        run.cell.config, run.units_per_step // run.chips)
    least_s = max(flops / run.peaks["bf16_flops_per_s"],
                  nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (took_ms * 1e-3)
