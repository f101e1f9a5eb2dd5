"""Device milliseconds a step under the program's `ssd_scan` scope in the
Granite cell: `ssd_scan_ms_per_step` itself, under a name of its own
because that metric's `workloads` hold the Nemotron cell alone. The
state-space scan of every Mamba-2 layer by itself, one group of B and C
over 64 heads at chunk 256, forward, backward and recomputed; the kernels'
gate refuses these shapes, so this is the `jnp` composition's time. Absent
where the run is untraced or the program has no such scope."""
from benchmark import manifest

_SAME = manifest.load_module("layer_metrics", "ssd_scan_ms_per_step")

LAYER = _SAME.LAYER
MOVES = _SAME.MOVES
UNIT = _SAME.UNIT
SOURCE = _SAME.SOURCE
read = _SAME.read
