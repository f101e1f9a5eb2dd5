"""Device milliseconds a step under the program's `mtp` scope
(`fluid.name_scope`, `paddle_tpu/models/glm4_moe_lite.py`): the whole
multi-token-prediction module, forward and backward: the combine (two
norms, the second look-up in the embedding table, W_eh), its expert layer
(`mtp/mla`, `mtp/moe_experts`, ...) and its head. `benchmark/part_scopes.py`
with `mtp` and `lm_head` as the part names: whatever lowers beneath `mtp`
is the module's, and a fusion XLA makes of the module's and the trunk
head's work (both score against the one W_head, whose gradient is the sum
of both uses) is counted under neither, as everywhere in this benchmark.
Absent where the run is untraced or the program has no such scope."""
from benchmark import part_scopes

LAYER = "mtp"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
PARTS = ("mtp",)
SPLIT = ("mtp", "lm_head")


def read(run):
    return part_scopes.part_ms_per_step(run, PARTS, SPLIT)
