"""Device milliseconds a step under the program's `ssd_scan` scope: the
state-space scan of every Mamba-2 layer by itself (the chunked products,
the decays and their running sums, the state carried between chunks),
forward, backward and recomputed (`benchmark/part_scopes.py`, with
`ssd_scan` added to its part names and the enclosing `mamba2` left out, so
that the scan is told from the rest of its mixer). Absent where the run is
untraced or the program has no such scope."""
from benchmark import part_scopes

LAYER = "kernels"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
PARTS = ("ssd_scan",)
AMONG = part_scopes.PARTS + PARTS


def read(run):
    return part_scopes.part_ms_per_step(run, PARTS, AMONG)
