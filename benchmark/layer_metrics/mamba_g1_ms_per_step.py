"""Device milliseconds a step under the program's `mamba2` scope in the
Granite cell: `mamba_ms_per_step` itself, under a name of its own because
that metric's `workloads` hold the Nemotron cell alone. The Mamba-2 mixers
whole (both projections, the convolution, the state-space scan at one group
of B and C over all heads, the gated norm over all of d_in), forward,
backward and recomputed. Absent where the run is untraced or the program
has no such scope."""
from benchmark import manifest

_SAME = manifest.load_module("layer_metrics", "mamba_ms_per_step")

LAYER = _SAME.LAYER
MOVES = _SAME.MOVES
UNIT = _SAME.UNIT
SOURCE = _SAME.SOURCE
read = _SAME.read
