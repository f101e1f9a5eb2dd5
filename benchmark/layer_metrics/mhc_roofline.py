"""Share of its roofline the hyper-connections' residual path reaches, per
cent: the least time the chip could take for it (`kernels/mhc.py`: the
streams read once and written once a sublayer and pass, over the HBM peak)
over the device time under the `mhc_map` and `mhc_mix` scopes."""
from benchmark import manifest, part_scopes

LAYER = "residual"
MOVES = "tokens_per_s_per_chip"
UNIT = "%"
SOURCE = "device_trace"
PARTS = ("mhc_map", "mhc_mix")


def read(run):
    took_ms = part_scopes.part_ms_per_step(run, PARTS)
    if not took_ms or run.peaks is None:
        return None
    nbytes = manifest.load_module("kernels", "mhc").step_bytes(
        run.cell.config, run.units_per_step // run.chips)
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / (took_ms * 1e-3)
