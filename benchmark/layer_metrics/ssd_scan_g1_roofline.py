"""Share of its roofline the Granite cell's state-space scan reaches, per
cent: the least time the chip could take for it (`kernels/ssd_scan_g1.py`,
which reads the `granitemoehybrid` keys into `kernels/ssd_scan.py`'s count:
three passes a Mamba-2 layer, the larger of the chunked form's arithmetic
over the bf16 peak and of x, dt, B, C read and y written once over the HBM
peak) over the device time under the `ssd_scan` scope."""
from benchmark import manifest, part_scopes

LAYER = "kernels"
MOVES = "tokens_per_s_per_chip"
UNIT = "%"
SOURCE = "device_trace"
PARTS = ("ssd_scan",)
AMONG = part_scopes.PARTS + PARTS


def read(run):
    took_ms = part_scopes.part_ms_per_step(run, PARTS, AMONG)
    if not took_ms or run.peaks is None:
        return None
    flops, nbytes = manifest.load_module("kernels", "ssd_scan_g1").step_work(
        run.cell.config, run.units_per_step // run.chips)
    least_s = max(flops / run.peaks["bf16_flops_per_s"],
                  nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (took_ms * 1e-3)
