"""Device milliseconds a step under the program's `mamba2` scope: the
Mamba-2 mixers whole (both projections, the convolution, the state-space
scan, the gated norm), forward, backward and recomputed
(`benchmark/part_scopes.py`, with `mamba2` added to its part names; the
scan's own scope `ssd_scan` lies inside and is not among them, so the scan
counts here). `rms_norm` is left out of the names: XLA fuses the block norm
in front of a mixer, and its transpose, into the mixer's in_proj products
(55 of 132 ms in the cell, my chip run, PR 34), and a fusion that mixes two
named parts would be counted under neither. Absent where the run is
untraced or the program has no such scope."""
from benchmark import part_scopes

LAYER = "mixer"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
PARTS = ("mamba2",)
AMONG = tuple(p for p in part_scopes.PARTS if p != "rms_norm") + PARTS


def read(run):
    return part_scopes.part_ms_per_step(run, PARTS, AMONG)
