"""Device milliseconds a step under the program's `mtp_head` scope: the
multi-token-prediction module's final norm, its logits over the held rows
of the vocabulary (the trunk's own W_head) and its loss, forward and
backward (`benchmark/part_scopes.py` with the two heads' names as the
parts). Beside `lm_head_ms_per_step` it shows what a second head costs.
Absent where the run is untraced or the program has no such scope."""
from benchmark import part_scopes

LAYER = "mtp"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
PARTS = ("mtp_head",)
HEADS = ("lm_head", "mtp_head")


def read(run):
    return part_scopes.part_ms_per_step(run, PARTS, HEADS)
