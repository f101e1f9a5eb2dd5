"""Device milliseconds a step under the program's `block_mlp` scope
(`fluid.name_scope`, `paddle_tpu/models/granite_hybrid.py`): every layer's
MLP sublayer whole, its norm, the SwiGLU's three products and its scaled
residual add, forward and backward (recomputed intermediates included).
`benchmark/part_scopes.py` with `block_mlp` and `mamba2` as the part
names: whatever lowers beneath `block_mlp` is the MLP's, and a fusion XLA
makes of an MLP's residual add and the next Mamba-2 mixer's products is
counted under neither. Absent where the run is untraced or the program has
no such scope."""
from benchmark import part_scopes

LAYER = "mlp"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
PARTS = ("block_mlp",)
SPLIT = ("block_mlp", "mamba2")


def read(run):
    return part_scopes.part_ms_per_step(run, PARTS, SPLIT)
