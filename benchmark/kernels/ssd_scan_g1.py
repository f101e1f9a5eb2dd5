"""The state-space scan of a Granite-4.0-H Mamba-2 layer: the count of
`kernels/ssd_scan.py`, read through the `granitemoehybrid` family's keys
(`models/granitemoehybrid.py:scan_keys`: `mamba_chunk_size`,
`mamba_n_heads`, `mamba_d_head`, `mamba_n_groups`, `mamba_d_state`, one
Mamba-2 layer an entry `mamba` of `layer_types`), so that there is one
count of the scan's work.

Bound at the published sizes: arithmetic, barely (chunk 256, one group of
128 over 64 heads of 64: 4.26 MFLOP and 17.2 KB a token and pass, 248
FLOPs a byte against the chip's ridge of 240).
"""
from benchmark import manifest

_SCAN = manifest.load_module("kernels", "ssd_scan")
_FAMILY = manifest.load_module("models", "granitemoehybrid")


def step_work(config: dict, tokens: int):
    """(FLOPs, HBM bytes) of all Mamba-2 layers' scans in one step."""
    return _SCAN.step_work(_FAMILY.scan_keys(config), tokens)
